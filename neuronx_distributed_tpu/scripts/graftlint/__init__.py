"""graftlint — repo-native static analysis for the jax_graft invariants.

An AST-based lint suite whose rule classes are distilled from this repo's
own incident history (each ``--explain RULE`` names the PR that bled for
it):

* **GL01 donation-aliasing** — host reads of ``donate_argnums`` trees
  (silently demote donation to a copy / read consumed buffers; PR 2).
* **GL02 host-sync-in-hot-path** — implicit device->host syncs in the
  modules whose sync counts are performance contracts (PR 2/PR 5).
* **GL03 recompile-hazard** — uncommitted long-lived scalars, module-level
  jit objects, mutable closure capture under jit (PR 4/PR 5).
* **GL04 explicit-SPMD seam bypass** — raw ``shard_map``/
  ``get_abstract_mesh`` outside ``parallel/mesh.py`` (the one seam that
  carries ``check_vma``/``axis_names`` and nested-region handling; PR 5).
* **GL05 nondeterminism** — unseeded/wall-clock RNG in library code
  (breaks bit-identical chaos/resume; PR 3/PR 5).
* **GL06 sharding-spec drift** — trailing-``None`` ``PartitionSpec``s at
  layout-commitment sites, raw ``NamedSharding`` in ``serving/`` outside
  the placement hooks (the PR 13 second-dispatch recompile).
* **GL07 trace-scope leakage** — ``tp_comms``/``fused_paged_attention_scope``
  entered manually, around a jit CONSTRUCTION, or re-entrantly
  (cross-engine trace contamination).
* **GL08 hold/refcount pairing** — except handlers that orphan allocator
  refs / staged holds / pins acquired in the try body (the PR 13
  staged-hold capacity leak).
* **GL09 labeled-metrics hygiene** — interpolated label values, dynamic
  label names (series collision/steering ahead of the exposition-time
  escaping).

The IR-level sibling — donation aliasing, transfer census and the
collective wire-byte ratchet verified on the LOWERED programs themselves
— is ``scripts/graftverify``.

Run it::

    python -m neuronx_distributed_tpu.scripts.graftlint [paths]

Suppress ONE finding with a documented reason::

    x = thing()  # graftlint: ok[GL02] the per-chunk sync the tests pin

Grandfathered debt lives in ``graftlint_baseline.json`` (ratchet: new
violations fail, fixed ones must be removed via ``--write-baseline``).
The repo-wide run is a tier-1 test (``tests/scripts/test_graftlint.py``).
"""

from neuronx_distributed_tpu.scripts.graftlint.core import Violation
from neuronx_distributed_tpu.scripts.graftlint.runner import Report, run, scan

__all__ = ["Violation", "Report", "run", "scan"]
