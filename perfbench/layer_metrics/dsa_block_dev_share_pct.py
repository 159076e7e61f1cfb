"""Device time of the sparse-attention block over busy time, traced window
(%): self time of the ops under the scopes ``dsa.index`` (the index
projections, the index key's LayerNorm, rotary, the ``k_idx`` write),
``dsa.score`` (decode: the index-score kernel; prefill: the scores, each
row's threshold and the byte mask), ``dsa.select`` (decode's ``top_k``),
``dsa.attend`` (decode: the sparse paged kernel; prefill: the byte-masked
flash kernel) and ``dsa.write`` (the window pages' copies into the K and V
pools), kernels included: each is called in its scope and named after it.
``None`` where the trace shows no ``dsa.*`` scope: the program has no
indexer."""
from perfbench import program_spans

SCOPE_PREFIX = "dsa."


def read(run):
    s = program_spans.scope_seconds(run)
    if not s or not s["busy_s"]:
        return None
    hit = sum(v for (base, parts), v in s["ops"].items()
              if base.startswith(SCOPE_PREFIX) or any(p.startswith(SCOPE_PREFIX) for p in parts))
    return 100.0 * hit / s["busy_s"] if hit else None
