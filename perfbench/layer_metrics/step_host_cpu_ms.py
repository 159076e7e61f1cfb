"""CPU time of the stepping thread in a decode-only step (ms): MEAN of the
stat ``cpu_us`` (``time.thread_time_ns`` across the step, set at the close of
``nxd.step`` by the engine's step ledger) over the ``nxd.step`` spans of the
traced window that hold a decode chunk and no prefill: the set
``step_host_ms`` uses. The mean and not the median: the chip's host counts a
thread's CPU time in ticks of 10 ms (PR 34), so one step reads 0 or 10,000 and
only the sum over the window's steps says anything (some ten ticks in 6 s:
read it to a third). Read beside ``step_host_ms``, the step's wall less its
readback: equal means the host's share is code running, far lower means the
thread is blocked inside the runtime's enqueue. A program without the stat:
``None``."""
from perfbench import program_spans as ps


def read(run):
    cpu = [float(step[2]["cpu_us"]) / 1e3 for step in ps.spans(run, ps.STEP)
           if "cpu_us" in step[2] and ps.children(run, step, ps.READBACK)
           and not ps.children(run, step, ps.PREFILL)]
    return sum(cpu) / len(cpu) if cpu else None
