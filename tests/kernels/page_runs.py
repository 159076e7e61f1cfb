"""Block tables with and without RUNS of adjacent pool pages, for the kernels
that fetch a slot's blocks through ``kernels/flash_decode._block_page_copies``
(one copy for an aligned group of ``PAGE_RUN`` table entries that reads ``p, p
+ 1, ...``, a copy a page for any other group). :data:`CASES` names the table
shapes; :func:`table` builds one; :func:`single_copies` is the form to hold a
kernel's output against, bit for bit: the same kernel with a run of ONE page,
which is a copy a page whatever the table reads (the form before runs)."""

import contextlib

import numpy as np

from neuronx_distributed_tpu.kernels import flash_decode

RUN = flash_decode.PAGE_RUN

# the table shapes: every mapped group a run; none; every other group; a run
# broken by the null page 0, and the ids 0, 1, 2, 3 (adjacent, but the null
# page's); adjacent ids that straddle two groups of the LOGICAL row; a row whose
# last block is short (its spare entries repeat the last page; the caller picks
# ``n_log``); a window layer's table, pages freed behind a floor mid-group
CASES = ("all_runs", "no_runs", "mixed", "null_page_in_a_run", "adjacent_off_the_grid", "short_last_block",
         "freed_behind_the_floor")

# pool pages below the first one a table maps: the null page and the ids 1, 2, 3
SPARE = RUN


def pool_pages(b: int, n_log: int) -> int:
    return SPARE + b * n_log


def table(case: str, b: int, n_log: int, spans) -> np.ndarray:
    """``(b, n_log)`` int32: slot ``i`` maps the pages ``[lo, hi)`` of
    ``spans[i]`` (``None``: nothing). Ids are ``SPARE + i * n_log + page``
    before the case rearranges them, so every aligned group starts as a run."""
    assert case in CASES, case
    ids = (SPARE + np.arange(b * n_log, dtype=np.int32)).reshape(b, n_log)
    groups = ids[:, :n_log // RUN * RUN].reshape(b, -1, RUN)      # a view
    if case == "no_runs":
        groups[...] = groups[:, :, ::-1].copy()
    elif case == "mixed":
        groups[:, 1::2] = groups[:, 1::2, ::-1].copy()
    elif case == "adjacent_off_the_grid":
        # logical pages 8q + 2 .. 8q + 5 read p .. p + 3, and no aligned group is a run
        groups[:, 0::2, :2] = groups[:, 0::2, 1::-1].copy()
        groups[:, 1::2, 2:] = groups[:, 1::2, :1:-1].copy()
    out = np.zeros((b, n_log), np.int32)
    for i, span in enumerate(spans):
        if span is not None:
            out[i, span[0]:span[1]] = ids[i, span[0]:span[1]]
    if case == "null_page_in_a_run":
        for i, span in enumerate(spans):
            if span is None:
                continue
            first = -(-span[0] // RUN) * RUN                      # the slot's first whole group
            if first + 2 * RUN <= span[1]:
                out[i, first + 2] = 0                             # p, p + 1, 0, p + 3
                out[i, first + RUN:first + 2 * RUN] = np.arange(RUN)   # 0, 1, 2, 3
    elif case == "freed_behind_the_floor":
        for i, span in enumerate(spans):
            if span is not None:
                out[i, :-(-span[0] // RUN) * RUN + 1] = 0         # the floor's group reads 0, p + 1, p + 2, p + 3
    return out


def runs(tbl: np.ndarray) -> int:
    """Aligned groups of ``tbl`` that read a run of adjacent pool pages (the
    serving pool's own count, over ``RUN``)."""
    from neuronx_distributed_tpu.serving.paging import PagedCacheManager

    return PagedCacheManager._count_pages("table", tbl)["table_pages_in_runs"] // RUN


@contextlib.contextmanager
def single_copies():
    """Inside: every page is fetched with a copy of its own."""
    was = flash_decode.PAGE_RUN
    flash_decode.PAGE_RUN = 1
    try:
        yield
    finally:
        flash_decode.PAGE_RUN = was
