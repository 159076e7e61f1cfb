"""GL04 — explicit-SPMD seam bypass."""

from __future__ import annotations

import ast
from typing import List

from neuronx_distributed_tpu.scripts.graftlint.analysis import AliasMap
from neuronx_distributed_tpu.scripts.graftlint.core import SourceFile, Violation

RULE = "GL04"
TITLE = "explicit-SPMD seam bypass"

EXPLAIN = """\
GL04 explicit-SPMD seam bypass

Every drop from GSPMD into an explicit-SPMD region goes through ONE seam,
parallel/mesh.py:

    jax.(experimental.)shard_map   -> mesh.compat_shard_map / manual_shard_map
    jax.sharding.get_abstract_mesh -> mesh.ctx_abstract_mesh

The seam carries what a raw call gets wrong one code path at a time:
`check_vma` off for the regions' collective-reduction outputs, the claimed
`axis_names`, and — in manual_shard_map — the context's AbstractMesh with
only the not-yet-manual axes when a region nests inside another (a Pallas
kernel under the pipeline engine's pp region). A raw call works on the path
a test happens to take and fails to lower on another, which is why this is
a lint rule, not a code review note. parallel/mesh.py itself is the one
exempt module (it IS the seam). `lax.axis_index` is plain JAX and is not
policed.
"""

_EXEMPT_SUFFIX = "parallel/mesh.py"

_BANNED_IMPORT_MODULES = ("jax.experimental.shard_map",)
_BANNED_PATHS = {
    "jax.shard_map": "use mesh.compat_shard_map (or mesh.manual_shard_map)",
    "jax.experimental.shard_map": "use mesh.compat_shard_map",
    "jax.experimental.shard_map.shard_map": "use mesh.compat_shard_map",
    "jax.sharding.get_abstract_mesh": "use mesh.ctx_abstract_mesh",
}


def check(src: SourceFile) -> List[Violation]:
    if src.relpath.endswith(_EXEMPT_SUFFIX):
        return []
    aliases = AliasMap(src.tree)
    out: List[Violation] = []

    def flag(node: ast.AST, what: str, fix: str) -> None:
        out.append(src.violation(
            RULE, node,
            f"raw {what} bypasses the explicit-SPMD seam — {fix} "
            "(parallel/mesh.py)",
        ))

    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in _BANNED_IMPORT_MODULES:
                    flag(node, f"import of {a.name}", "use mesh.compat_shard_map")
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module in _BANNED_IMPORT_MODULES:
                flag(node, f"import from {node.module}",
                     "use mesh.compat_shard_map")
            for a in node.names:
                full = f"{node.module}.{a.name}"
                if full in _BANNED_PATHS:
                    flag(node, full, _BANNED_PATHS[full])
        elif isinstance(node, (ast.Attribute, ast.Name)):
            path = aliases.resolve(node)
            if path in _BANNED_PATHS:
                # skip the inner Name/Attribute of a chain we already
                # flagged at the outermost matching node
                flag(node, path, _BANNED_PATHS[path])

    # one finding per source line: the Attribute walk sees both the outer
    # chain and pieces of it when aliased imports overlap
    seen = set()
    deduped = []
    for v in out:
        if (v.line, v.rule) not in seen:
            seen.add((v.line, v.rule))
            deduped.append(v)
    return deduped
