#!/usr/bin/env python
"""Offline checkpoint conversion CLI (reference:
``optimizer/convert_zero_checkpoints.py`` ``nxd_convert_zero_checkpoints``
— merge DP-sharded ZeRO-1 optimizer states to full and re-shard to a new DP
degree, :55-179).

The reference needs this tool because its checkpoints are per-rank shard
files whose layout bakes in the DP degree. This framework's checkpoints are
GLOBAL logical arrays (orbax/tensorstore): any (dp, tp, pp, ep) re-layout
happens at load time by restoring against ``NamedSharding`` targets
(``trainer.checkpoint.load_checkpoint(items_target=...)``), so the
merge/re-shard operations are identity transforms by construction. What
remains useful offline, and what this CLI provides:

* ``verify``   — open every item, checking the done-marker protocol and that
  all tensors deserialize (the reference's integrity pass);
* ``strip``    — re-save with the optimizer state dropped (a servable
  model-only checkpoint, the usual reason to merge ZeRO shards);
* ``copy``     — round-trip a checkpoint into a new directory/tag (e.g.
  local disk → ``gs://`` bucket), re-serializing through orbax.
"""

from __future__ import annotations

import argparse

from neuronx_distributed_tpu.trainer.checkpoint import (
    load_checkpoint,
    save_checkpoint,
)
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)


def verify(checkpoint_dir: str, tag: str | None) -> dict:
    items, user_content, tag = load_checkpoint(checkpoint_dir, tag)
    import jax

    counts = {
        name: len(jax.tree.leaves(tree)) for name, tree in items.items()
    }
    logger.info("checkpoint '%s' OK: %s tensors per item", tag, counts)
    return counts


def strip_optimizer(checkpoint_dir: str, output_dir: str, tag: str | None,
                    out_tag: str | None) -> None:
    items, user_content, tag = load_checkpoint(checkpoint_dir, tag)
    kept = {k: v for k, v in items.items() if k != "optimizer"}
    if len(kept) == len(items):
        logger.warning("no 'optimizer' item found in '%s'; copying as-is", tag)
    save_checkpoint(output_dir, out_tag or tag, items=kept,
                    user_content=user_content)


def copy(checkpoint_dir: str, output_dir: str, tag: str | None,
         out_tag: str | None) -> None:
    items, user_content, tag = load_checkpoint(checkpoint_dir, tag)
    save_checkpoint(output_dir, out_tag or tag, items=items,
                    user_content=user_content)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("command", choices=["verify", "strip", "copy"])
    p.add_argument("--input", required=True, help="checkpoint dir (local or gs://)")
    p.add_argument("--output", default=None, help="output dir (strip/copy)")
    p.add_argument("--tag", default=None, help="source tag (default: newest)")
    p.add_argument("--out-tag", default=None, help="destination tag")
    args = p.parse_args()
    if args.command == "verify":
        verify(args.input, args.tag)
    elif args.command == "strip":
        if not args.output:
            p.error("strip requires --output")
        strip_optimizer(args.input, args.output, args.tag, args.out_tag)
    else:
        if not args.output:
            p.error("copy requires --output")
        copy(args.input, args.output, args.tag, args.out_tag)


if __name__ == "__main__":
    main()
