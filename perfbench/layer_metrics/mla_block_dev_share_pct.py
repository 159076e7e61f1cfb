"""Device time of the latent attention block over busy time, traced window
(%): self time of the ops under the scopes ``mla.compress`` (``W_kv_a``, the
latent's norm, rope, the cache write), ``mla.absorb`` (``q_nope W_uk`` before
the decode kernel, ``W_uv`` after it) and ``mla.expand`` (prefill's
``W_kv_b``), plus the attention kernels themselves, which are named after the
method that calls them (``attn._cached_attention``). ``None`` where the trace
shows no ``mla.*`` scope: the program is not a latent-attention one."""
from perfbench import program_spans

SCOPE_PREFIX = "mla."
KERNEL = "attn._cached_attention"


def read(run):
    s = program_spans.scope_seconds(run)
    if not s or not s["busy_s"]:
        return None
    scoped = sum(v for (_, parts), v in s["ops"].items() if any(p.startswith(SCOPE_PREFIX) for p in parts))
    if not scoped:
        return None
    kernels = sum(v for (base, _), v in s["ops"].items() if base.startswith(KERNEL))
    return 100.0 * (scoped + kernels) / s["busy_s"]
