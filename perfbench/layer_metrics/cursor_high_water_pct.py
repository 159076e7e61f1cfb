"""How far the engine's shared write cursor came along its cache row (%):
100 x the largest ``cursor / row_columns`` over the traced window's
``nxd.step.decode.dispatch`` spans (stats ``cursor``: the column the chunk
starts to write at, ``self.cache.cursor``; ``row_columns``: the row it runs
against, ``max_seq_len``). Every slot writes at the one cursor, so a faster
step moves it faster: at 100 the engine preempts every request and rewinds.
In a closed loop the cursor only moves forward, so the trace's last seconds
hold the window's high water. A program without the stats: ``None``."""
from perfbench import program_spans


def read(run):
    shares = [float(s["cursor"]) / float(s["row_columns"])
              for _, _, s, _ in program_spans.spans(run, program_spans.DISPATCH)
              if "cursor" in s and s.get("row_columns")]
    return 100.0 * max(shares) if shares else None
