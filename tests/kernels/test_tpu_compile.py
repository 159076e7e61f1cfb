"""The chip's compiler, asked without the chip (on-chip-measurement guide §2.3).

libtpu compiles for a DESCRIBED v5e topology from this CPU process, so what
Mosaic refuses — a block it cannot tile, a kernel it cannot partition, a
program that does not fit the HBM — fails here, at no chip time. Interpret
mode cannot see any of that: every kernel below had passed its interpret-mode
tests while three of the four families were refused at Llama-2-7B geometry.

The kernel compiles are tier-1 (a second or two each). The whole-program
compiles — the train step, the engine's decode chunk and a prefill bucket,
on one described chip and on the 2x2 mesh — are ``slow``: they are the
rehearsal to run before a chip call is spent, not part of every test run.

Nothing runs, so nothing here says anything about results or speed.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.flash_attention import flash_attention
from neuronx_distributed_tpu.kernels.flash_decode import (
    flash_decode_attention,
    paged_flash_decode_attention,
)
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, llama2_7b
from neuronx_distributed_tpu.parallel import mesh as mesh_lib

# Llama-2-7B head geometry (models/llama.py llama2_7b) and its GQA(8) sibling
H, D = 32, 128
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) v5e 2x2 topology; skip where libtpu
    cannot describe one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no such topology on this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    """Kernels uninterpreted, platform choices taken as on the TPU, and the
    persistent cache off (a described-device executable is written to it but
    cannot be read back without a chip — every later run would warn)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(backend, "INTERPRET", False)
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return spec


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (?:\(.*?\)|\S+) ([a-z][\w\-]*)\(")


def _ops(hlo_text):
    """``[(opcode, custom-call target or None)]``, an entry an instruction of
    a compiled module: its OPS. A word looked for in the module's whole TEXT
    also finds the header's table of every function name the process has
    traced and each instruction's ``op_name``: what the xdist worker ran
    before, not what this program does."""
    ops = []
    for lines in _computations(hlo_text).values():
        for line in lines:
            made = _INSTRUCTION.match(line)
            if made:
                target = re.search(r'custom_call_target="([^"]*)"', line)
                ops.append((made.group(1), target and target.group(1)))
    return ops


def _kernels(hlo_text):
    """How many Pallas kernels a compiled module calls."""
    return sum(target == KERNEL for _, target in _ops(hlo_text))


# --- the kernels alone ---------------------------------------------------------


@pytest.mark.parametrize("hkv", [32, 8], ids=["mha", "gqa8"])
def test_flash_fwd_bwd_compiles(topo, hkv):
    s = _one_chip(topo)
    q, kv = s((4, 2048, H, D)), s((4, 2048, hkv, D))

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    assert _kernels(_compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv))


def test_flash_with_segment_ids_compiles(topo):
    """Packed documents (``--data packed:``) and every padded prefill."""
    s = _one_chip(topo)
    q, seg = s((4, 2048, H, D)), s((4, 2048), jnp.int32)

    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, segment_ids=seg)
        return out.astype(jnp.float32).sum()

    assert _kernels(_compiled_text(
        jax.grad(loss, argnums=(0, 1, 2)), q, q, q, seg
    ))


@pytest.mark.parametrize("hkv", [32, 8], ids=["mha", "gqa8"])
def test_flash_decode_with_kv_valid_compiles(topo, hkv):
    """The row-cache decode path at context >= FLASH_DECODE_MIN_CONTEXT —
    always called with ``kv_valid`` by the engine."""
    s = _one_chip(topo)
    q, cache = s((8, 1, H, D)), s((8, 4096, hkv, D))
    pos, valid = s((1,), jnp.int32), s((8, 4096), jnp.bool_)
    assert _kernels(_compiled_text(
        flash_decode_attention, q, cache, cache, pos, valid
    ))


@pytest.mark.parametrize("hkv", [32, 8], ids=["mha", "gqa8"])
def test_paged_decode_compiles(topo, hkv):
    """The engine's default decode transport on a TPU: page 16."""
    s = _one_chip(topo)
    q, pool = s((8, 1, H, D)), s((2048, 16, hkv, D))
    table, pos = s((8, 256), jnp.int32), s((1,), jnp.int32)
    valid = s((8, 4096), jnp.bool_)

    def fn(q, k, v, table, pos, valid):
        return paged_flash_decode_attention(
            q, k, v, table, pos, valid, page_size=16
        )

    assert _kernels(_compiled_text(fn, q, pool, pool, table, pos, valid))


# DeepSeek-V2-Lite's attention geometry (models/deepseek_v2.py): 16 heads, a
# latent of 512 and a rotated key of 64 a token, q.k over 192, values of 128
MLA = dict(h=16, d_c=512, d_r=64, d_qk=192, d_v=128)


def test_paged_latent_decode_compiles(topo):
    """The absorbed-decode kernel at the benchmark cell's size: 8 slots of
    32,768 columns, page 16, 16 heads against ONE row of 512 + 64. Neither
    pool leaf may be copied whole to be fed to the kernel but the rotated
    key's (a ninth of the bytes: its 64-wide layout has the page index
    minor, ``kernels/flash_decode.py``). The kernel fetches pages itself:
    each pool leaf is ONE operand of the call, not one a page of a block, and
    its two blocks in flight and its accumulators sit well inside the 16 MiB
    of VMEM a kernel may scope on a v5e (Mosaic refuses the compile past it)."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        LATENT_BLOCK_TOKENS,
        paged_latent_decode_attention,
    )

    s = _one_chip(topo)
    slots, seq, pages = 8, 32768, 8 * 32768 // 16 + 1
    q_c, q_r = s((slots, 1, MLA["h"], MLA["d_c"])), s((slots, 1, MLA["h"], MLA["d_r"]))
    c_pool, r_pool = s((pages, 16, 1, MLA["d_c"])), s((pages, 16, 1, MLA["d_r"]))
    table, pos = s((slots, seq // 16), jnp.int32), s((1,), jnp.int32)
    valid = s((slots, seq), jnp.bool_)

    def fn(q_c, q_r, c_pool, r_pool, table, pos, valid):
        return paged_latent_decode_attention(
            q_c, q_r, c_pool, r_pool, table, pos, valid, scale=0.1147,
            page_size=16,
        )

    text = _compiled_text(fn, q_c, q_r, c_pool, r_pool, table, pos, valid)
    latent = "bf16[%d,16,1,%d]" % (pages, MLA["d_c"])
    assert not [ln for ln in text.splitlines() if " copy(" in ln and latent in ln]
    (call,) = [ln for ln in text.splitlines() if "custom-call(" in ln and KERNEL in ln]
    operands = call[call.index("custom-call(") + len("custom-call("):]
    operands = re.sub(r"/\*.*?\*/", "", operands[:operands.index(")")]).split(",")
    # block table, its runs, live blocks, spans | positions, kv_valid, q_c, q_r | the two pool leaves
    assert len(operands) == 10, operands
    assert len(set(op.strip() for op in operands)) == 10, operands
    # two blocks of T tokens in flight (the 64-wide leaf in tiles of 128 lanes),
    # float32 accumulators for 16 rows, a slot's kv_valid row double-buffered
    vmem = (2 * LATENT_BLOCK_TOKENS * (MLA["d_c"] + 128) * 2
            + MLA["h"] * (MLA["d_c"] + 2) * 4 + 2 * seq * 4)
    assert vmem < 16 * 1024**2 / 4


def _run_copies(jaxpr, leaf):
    """The ``dma_start`` equations of a traced kernel (every nested jaxpr
    walked) whose source is ``PAGE_RUN`` pages of a pool leaf ``(page, *leaf)``."""
    from neuronx_distributed_tpu.kernels.flash_decode import PAGE_RUN

    found = []

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "dma_start":
                tree = jax.tree.unflatten(e.params["tree"], e.invars)
                src, transforms = tree[0], tree[1]
                shape = src.aval.shape
                for t in transforms:
                    shape = t.get_indexer_shape() if hasattr(t, "get_indexer_shape") else shape
                if tuple(shape) == (PAGE_RUN, 16) + tuple(leaf):
                    found.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            walk(e.params["jaxpr"])
    return found


@pytest.mark.parametrize("name", ["zaya1_quarter_tile", "trinity_joined", "dsv2lite_latent", "keye_index_key"])
def test_block_walking_kernels_fetch_a_run_of_pages_with_one_copy(topo, name):
    """The three kernels that fetch a slot's blocks themselves, at the cells'
    leaves: ZAYA1's ``(4, 128)`` (a 16 KB page, a 64 KB run), Trinity's ``(16,
    128)`` (a 256 KB run), DeepSeek-V2-Lite's latent of 512 lanes beside a
    rotated key of 64 (held in tiles of 128: the copy names the tile's lanes,
    ``_hbm_lanes``, of FOUR pages), and the 64-lane index key. Mosaic takes a
    slice of ``PAGE_RUN`` pool pages as ONE copy's source into the block's
    pages in VMEM, and the traced kernel starts such a copy for each leaf."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_index_scores,
        paged_latent_decode_attention,
        paged_walk_decode_attention,
    )

    s = _one_chip(topo)
    page = 16
    pos = s((1,), jnp.int32)
    if name in ("zaya1_quarter_tile", "trinity_joined"):
        b, n_log, heads, rows = (32, 1024, 8, 4) if name == "zaya1_quarter_tile" else (8, 2048, 48, 16)
        fn = lambda q, pool, bt, at, ok: paged_walk_decode_attention(q, pool, bt, at, kv_valid=ok, page_size=page)  # noqa: E731
        args = (s((b, 1, heads, 128)), s((b * n_log + 1, page, rows, 128)))
        leaves = [(rows, 128)]
    elif name == "dsv2lite_latent":
        b, n_log = 8, 2048
        fn = lambda qc, qr, c, r, bt, at, ok: paged_latent_decode_attention(  # noqa: E731
            qc, qr, c, r, bt, at, ok, scale=0.1147, page_size=page)
        args = (s((b, 1, MLA["h"], MLA["d_c"])), s((b, 1, MLA["h"], MLA["d_r"])),
                s((b * n_log + 1, page, 1, MLA["d_c"])), s((b * n_log + 1, page, 1, MLA["d_r"])))
        leaves = [(MLA["d_c"],), (128,)]          # the 64-wide leaf's copy names its tile's 128 lanes
    else:
        b, n_log = 8, 2048
        fn = lambda q, w, pool, bt, at, ok: paged_index_scores(q, w, pool, bt, at, ok, page_size=page)  # noqa: E731
        args = (s((b, 1, 16, 64)), s((b, 1, 16)), s((b * n_log + 1, page, 1, 64)))
        leaves = [(128,)]
    args = args + (s((b, n_log), jnp.int32), pos, s((b, n_log * page), jnp.bool_))
    assert _kernels(_compiled_text(fn, *args)) == 1
    jaxpr = jax.make_jaxpr(fn)(*args)
    for leaf in leaves:
        assert _run_copies(jaxpr, leaf), f"no copy of a run of pages of the {leaf} leaf"


def test_sparse_decode_kernels_compile_at_keye_geometry(topo):
    """Keye-VL-2.0's decode under its indexer at the serve cell's shapes (8
    slots of 32,768 columns, page 16, 32 Q / 4 KV heads of 128, 16 index
    heads of 64, 2048 kept): the index-score kernel over the 64-wide leaf
    (its page copies name the tile's 128 lanes) and the sparse kernel that
    fetches a token's joined (8, 128) K/V leaf with one copy and waits once a
    chunk over the whole buffer; and the byte-masked flash
    forward of a 24,576-token prefill with the kernel that builds its mask
    (64 query rows' scores as int32 keys in 6 MiB of VMEM). None may copy a pool leaf whole but
    the index kernel's 64-wide one (the layout conversion PR 26 found)."""
    from neuronx_distributed_tpu.kernels.flash_attention import (
        masked_flash_attention,
        sparse_keep_mask_kernel,
    )
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_index_scores,
        paged_sparse_decode_attention,
    )

    s = _one_chip(topo)
    b, n_log, page, keep = 8, 2048, 16, 2048
    pages = b * n_log + 1
    table, valid = s((b, n_log), jnp.int32), s((b, n_log * page), jnp.bool_)
    text = _compiled_text(
        lambda q, w, pool, bt, pos, ok: paged_index_scores(q, w, pool, bt, pos, ok, page_size=page),
        s((b, 1, 16, 64)), s((b, 1, 16)), s((pages, page, 1, 64)), table, s((1,), jnp.int32), valid)
    assert _kernels(text)
    text = _compiled_text(
        lambda q, kv, bt, cols, n: paged_sparse_decode_attention(q, kv, bt, cols, n, page_size=page),
        s((b, 1, H, D)), s((pages, page, 2 * 4, D)), table, s((b, keep), jnp.int32), s((b,), jnp.int32))
    assert _kernels(text)
    assert not re.search(r"bf16\[%d,%d,8,%d\]\S* copy\(" % (pages, page, D), text), "the K/V pool leaf is copied whole"
    seq = 24576
    text = _compiled_text(
        lambda q, k, v, m, ok: masked_flash_attention(q, k, v, m, ok),
        s((1, seq, H, D)), s((1, seq, 4, D)), s((1, seq, 4, D)), s((1, seq, seq), jnp.int8), s((1, seq), jnp.bool_))
    assert _kernels(text)
    text = _compiled_text(
        lambda q, w, k, ok: sparse_keep_mask_kernel(q, w, k, ok, keep),
        s((1, seq, 16, 64)), s((1, seq, 16)), s((1, seq, 64)), s((1, seq), jnp.bool_))
    assert _kernels(text)
    # a bucket under the mask kernel's 512-wide key tile is padded up to it:
    # the TPU has ONE prefill form (both kernels), whatever the prompt
    from neuronx_distributed_tpu.modules.attention import sparse_prefill_attention

    short = 128
    text = _compiled_text(
        lambda q, k, v, qi, wi, ki, ok: sparse_prefill_attention(q, k, v, qi, wi, ki, keep, "flash", ok),
        s((1, short, H, D)), s((1, short, 4, D)), s((1, short, 4, D)), s((1, short, 16, 64)),
        s((1, short, 16)), s((1, short, 1, 64)), s((1, short), jnp.bool_))
    assert _kernels(text) >= 2


@pytest.mark.parametrize("seq", [2048, 20992, 32768])
def test_flash_prefill_with_a_narrower_value_head_compiles(topo, seq):
    """MLA's materialised prefill: q and k of 192, v of 128, with the
    padding mask every engine prefill carries; 20,608 is the cell's longest
    prompt rounded up to a multiple of 512, the flash kernel's block."""
    s = _one_chip(topo)
    qk = s((1, seq, MLA["h"], MLA["d_qk"]))
    v, seg = s((1, seq, MLA["h"], MLA["d_v"])), s((1, seq), jnp.int32)

    def fn(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg)

    assert _kernels(_compiled_text(fn, qk, qk, v, seg))


@pytest.mark.parametrize("residuals", [False, True], ids=["forward_only", "residuals_kept"])
@pytest.mark.parametrize("bucket", [16384, 20992])
def test_classed_flash_forward_compiles_at_the_docs_cells_geometry(topo, bucket, residuals):
    """The forward since PR 45 at cell 4's prefill (16 heads, q/k 192, v 128,
    the 16,384 and 20,992 buckets, batch 1): the grid walks the causal
    triangle's pairs from prefetched tables (528 and 861 steps, not 1,024 and
    1,681), the class and the block to fetch in one int32 a pair; forward-only
    (no ``lse`` output) and as the backward's residuals keep it."""
    from neuronx_distributed_tpu.kernels.flash_attention import _flash_fwd, _tile_pairs

    s = _one_chip(topo)
    qk, v = s((1, MLA["h"], bucket, MLA["d_qk"])), s((1, MLA["h"], bucket, MLA["d_v"]))
    n = bucket // 512
    assert _tile_pairs(n, n, 512, 512, triangle=True)[0].size == n * (n + 1) // 2

    def fn(q, k, v, seg):
        return _flash_fwd(q, k, v, True, 512, 512, False, q_seg=seg, k_seg=seg, residuals=residuals)

    text = _compiled_text(fn, qk, qk, v, s((1, bucket), jnp.int32))
    assert _kernels(text) and f"s32[1,{n * (n + 1) // 2}]" in text        # the plan: a row a batch row, an entry a pair


def test_flash_backward_with_a_narrower_value_head_compiles(topo):
    s = _one_chip(topo)
    qk, v = s((2, 2048, MLA["h"], MLA["d_qk"])), s((2, 2048, MLA["h"], MLA["d_v"]))

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    assert _kernels(_compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v))


def test_mixtral_width_blockwise_moe_compiles(topo):
    """One Mixtral-8x7B-width expert layer forward on the dropless
    blockwise path: ``jax.lax.ragged_dot``, which the TPU compiler turns
    into its own Mosaic grouped-matmul kernel."""
    from neuronx_distributed_tpu.modules.moe.expert_mlps import ExpertMLPs

    s = _one_chip(topo)
    tokens, experts, k = 4096, 8, 2
    layer = ExpertMLPs(
        num_experts=experts, hidden_size=4096, intermediate_size=14336,
        top_k=k, strategy="blockwise", dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    )
    x = s((tokens, 4096))
    top_e, top_w = s((tokens, k), jnp.int32), s((tokens, k))
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(
            layer.init, jax.random.PRNGKey(0),
            *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, top_e, top_w)),
        ),
    )
    assert _kernels(_compiled_text(layer.apply, params, x, top_e, top_w))


@pytest.mark.parametrize("name,experts,hidden,inter,k,rows", [
    ("deepseek-v2-lite", 64, 2048, 1408, 6, 8),
    ("keye-vl2", 128, 2048, 768, 8, 8),
    ("mixtral-8x7b", 8, 4096, 14336, 2, 16),
    ("mixtral-8x7b-at-the-limit", 8, 4096, 14336, 2, 256),
])
def test_streamed_expert_mlp_compiles_at_the_cells_shapes(topo, name, experts, hidden, inter, k, rows):
    """``kernels/moe_stream.py`` through the layer, as a decode step of the
    three expert cells calls it (and at ``MOE_STREAM_MAX_TOKENS`` rows): whole
    experts a grid step at DeepSeek's and Keye's shapes, tiles of 1024 at
    Mixtral's; what it asks of VMEM is stated (under 64 MiB at the cells' rows,
    under 80 at the limit, of the chip's 128), and Mosaic refuses a kernel
    that needs more than it asked for."""
    from neuronx_distributed_tpu.kernels import moe_stream
    from neuronx_distributed_tpu.modules.moe.expert_mlps import MOE_STREAM_MAX_TOKENS, ExpertMLPs

    assert rows <= MOE_STREAM_MAX_TOKENS
    s = _one_chip(topo)
    layer = ExpertMLPs(
        num_experts=experts, hidden_size=hidden, intermediate_size=inter,
        top_k=k, strategy="blockwise", dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
    )
    x, top_e, top_w = s((rows, hidden)), s((rows, k), jnp.int32), s((rows, k), jnp.float32)
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(
            layer.init, jax.random.PRNGKey(0),
            *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (x, top_e, top_w)),
        ),
    )
    text = _compiled_text(layer.apply, params, x, top_e, top_w)
    ops = _ops(text)
    assert [target for _, target in ops if target == KERNEL] == [KERNEL]     # the streamed form, one call
    assert not [op for op in ops if "ragged" in op[0] or "ragged" in (op[1] or "")]   # and no grouped matmul
    tile = moe_stream.pick_block_i(hidden, inter, 2, True)
    assert tile == (inter if inter < 2048 else 1024)
    asked = moe_stream.vmem_limit_bytes(rows, hidden, tile, 2, True)
    assert 2 * 3 * hidden * tile * 2 < asked < (64 if rows <= 16 else 80) * 1024**2


# --- whole programs (slow: the rehearsal before a chip call) -------------------


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        tree, shardings,
    )


def _train_step_compiled(topo, n_devices, *, tp, sp, layers=2, batch=4,
                         seq=2048):
    """The Trainer's own step (``build_train_step`` with the anomaly guard,
    as ``Trainer.fit`` builds it) over abstract state on described devices."""
    from flax.core import meta

    from neuronx_distributed_tpu.optim.zero1 import zero1_shardings_for_opt_state
    from neuronx_distributed_tpu.parallel.sharding import param_shardings
    from neuronx_distributed_tpu.trainer import OptimizerConfig, make_optimizer
    from neuronx_distributed_tpu.trainer.trainer import (
        AnomalyGuardConfig,
        TrainState,
        build_train_step,
    )

    mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=topo.devices[:n_devices]
    )
    mesh = mesh_lib.get_mesh()
    cfg = llama2_7b(num_layers=layers, max_seq_len=seq, sequence_parallel=sp)
    model = LlamaForCausalLM(cfg, attention_impl="auto")
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    boxed = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    p_sh = param_shardings(boxed)
    params = _abstract(meta.unbox(boxed), p_sh)
    opt_cfg = OptimizerConfig()
    optimizer = make_optimizer(opt_cfg)
    opt_shapes = jax.eval_shape(optimizer.init, params)
    s_sh = zero1_shardings_for_opt_state(
        opt_shapes, params, jax.tree.map(lambda s: s.spec, p_sh), mesh=mesh,
        enabled=opt_cfg.zero1,
    )
    repl = NamedSharding(mesh, P())
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=repl)  # noqa: E731
    state = TrainState(
        step=scalar(jnp.int32), params=params,
        opt_state=_abstract(opt_shapes, s_sh),
        guard={"gnorm_ema": scalar(jnp.float32),
               "good_steps": scalar(jnp.int32), "skips": scalar(jnp.int32)},
    )
    data = NamedSharding(mesh, P(mesh_lib.DATA_AXES, mesh_lib.CP_AXIS))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
    step = build_train_step(
        model, optimizer, p_sh, s_sh,
        max_grad_norm=opt_cfg.max_grad_norm,
        anomaly_guard=AnomalyGuardConfig(),
    )
    batch_abs = {"input_ids": tok, "labels": tok,
                 "loss_mask": jax.ShapeDtypeStruct(
                     (batch, seq), jnp.float32, sharding=data)}
    return step.lower(state, batch_abs).compile()


def _engine_programs(topo, n_devices, *, layers=8, slots=8, seq=4096,
                     page=16, bucket=512, model=None):
    """``(engine, lower_decode, lower_prefill, pool_shards)`` for a
    default-configured engine over abstract params on described devices;
    ``pool_shards`` are the shapes of the bf16 page-pool leaves as ONE
    device holds them (``(2049, 16, 32, 128)``). ``model``: another model
    than Llama-2-7B's, built for ``seq``. With ``n_devices`` > 1
    the global tp mesh is up and every operand carries the placement the TP
    engine's partitioner would commit — the engine's programs take their
    sharding from their operands, so this IS the tp program."""
    from neuronx_distributed_tpu.parallel.sharding import (
        ServingPartitioner,
        serving_mesh,
    )
    from neuronx_distributed_tpu.serving import ServingEngine
    from neuronx_distributed_tpu.serving.paging import PagedCacheManager

    if model is None:
        cfg = llama2_7b(
            num_layers=layers, max_seq_len=seq, scan_layers=False,
            remat=False, param_dtype=jnp.bfloat16,
        )
        model = LlamaForCausalLM(cfg, attention_impl="auto")
    boxed = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    )
    if n_devices > 1:
        part = ServingPartitioner(
            serving_mesh(n_devices, devices=topo.devices[:n_devices])
        )
        values, p_sh = part.param_shardings(boxed)
        params = _abstract(values, p_sh)
        kv_shardings = part.kv_shardings
        repl = NamedSharding(part.mesh, P())
    else:
        from flax.core import meta

        repl = SingleDeviceSharding(topo.devices[0])
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl),
            meta.unbox(boxed),
        )
        kv_shardings = lambda tree: jax.tree.map(lambda _: repl, tree)  # noqa: E731
    engine = ServingEngine(
        model, params, num_slots=slots, kv_page_size=page,
    )
    put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=repl)  # noqa: E731
    prefill = engine._prefill_fn(bucket)
    ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=repl)
    mask = jax.ShapeDtypeStruct((1, bucket), jnp.bool_, sharding=repl)

    def lower_prefill():
        return prefill.lower(params, ids, mask)

    row = jax.eval_shape(lambda p, i, m: prefill(p, i, m)[1], params, ids, mask)

    def pool_of(row):
        # a model with window layers: a block table and a pool a layer kind
        mgr = PagedCacheManager(slots, seq, page, window=engine.cache.window,
                                window_write_cols=engine.decode_chunk_size)
        mgr.allocate_from(row)
        return mgr.cache

    paged = jax.eval_shape(pool_of, row)
    paged = _abstract(paged, kv_shardings(paged))
    state = jax.tree.map(put, jax.eval_shape(engine._fresh_slot_state))

    def lower_decode():
        return engine._decode_chunk.lower(params, paged, state)

    pool_shards = [
        a.sharding.shard_shape(a.shape)
        for a in jax.tree.leaves(paged["pool"]) if a.ndim == 4
    ]
    return engine, lower_decode, lower_prefill, pool_shards


def _computations(hlo_text):
    """``{name: [instruction lines]}`` of a compiled module's text."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


def _copies_inside_loops(hlo_text, shapes):
    """``copy`` instructions of one of ``shapes`` in any computation a
    ``while`` body reaches (its conditional's branches included): what runs
    once per decode STEP, against once per chunk."""
    comps = _computations(hlo_text)
    todo = [
        body
        for lines in comps.values() for line in lines if " while(" in line
        for body in re.findall(r"body=%?([\w.\-]+)", line)
    ]
    assert todo, "the decode program holds no while loop"
    reached = set()
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached.add(name)
        for line in comps[name]:
            for one, many in _CALLED.findall(line):
                todo.extend(
                    [one] if one
                    else [c.strip().lstrip("%") for c in many.split(",")]
                )
    found = []
    for name in reached:
        for line in comps[name]:
            made = re.search(r"= (\w+\[[\d,]*\])\S* copy\(", line)
            if made and made.group(1) in shapes:
                found.append(f"{name}: {line.strip()[:100]}")
    return found


def _arrays_of_a_views_size(hlo_text, pool_shards):
    """bf16 arrays in a compiled module's text that hold as many values as a
    logical K/V leaf (``slots x row x heads x width``, whatever their dims):
    a default pool is that and the null page, so no pool leaf counts."""
    views = {(s[0] - 1) * math.prod(s[1:]) for s in pool_shards}
    return sorted({
        made.group(0) for made in re.finditer(r"bf16\[([\d,]+)\]", hlo_text)
        if math.prod(map(int, made.group(1).split(","))) in views
    })


def _assert_pool_carried_and_no_view(compiled, pool_shards, temp_gib):
    """PR 25: the fused chunk carries its page pool through the scan, so the
    window scatter of every step and layer writes the carried buffer. Closed
    over, each pool leaf was copied whole inside the loop (once per layer,
    per K and V, per step) before its scatter. PR 27: the chunk stages its
    tokens in its write window, so the program holds no array of a logical
    leaf's size anywhere, and its temporaries are what the described-v5e
    compile showed (``temp_gib``: the weights' layout copies, hoisted out of
    the scan) and a tenth: well under ONE view, where it held two."""
    text = compiled.as_text()
    shapes = {"bf16[%s]" % ",".join(map(str, s)) for s in pool_shards}
    copies = _copies_inside_loops(text, shapes)
    assert not copies, (
        f"{len(copies)} whole-pool copies per decode step: " + "; ".join(copies[:3])
    )
    views = _arrays_of_a_views_size(text, pool_shards)
    assert not views, f"the decode program builds a logical K/V view: {views}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    one_view = sum(2 * (s[0] - 1) * math.prod(s[1:]) for s in pool_shards)
    assert temp < min(1.1 * temp_gib * 2**30, 0.5 * one_view), (
        f"{temp / 2**30:.2f} GiB of temporaries, {temp_gib} when this bound "
        f"was taken; one K/V view is {one_view / 2**30:.2f} GiB"
    )


def _fits(compiled, hbm_bytes=16 * 1024**3):
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert live < hbm_bytes, f"program needs {live / 2**30:.1f} GiB per device"
    return live


@pytest.mark.slow
def test_train_step_compiles_at_7b_widths(topo):
    compiled = _train_step_compiled(topo, 1, tp=1, sp=False)
    assert _kernels(compiled.as_text())
    _fits(compiled)


@pytest.mark.slow
def test_engine_programs_compile_at_7b_widths(topo):
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1
    )
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_fused",
        "paged_attention": "fused",
    }
    for lower in (lower_decode, lower_prefill):
        compiled = lower().compile()
        assert _kernels(compiled.as_text())
        _fits(compiled)
        if lower is lower_decode:
            # 0.81 GiB beside a view of 4 (described-v5e compile, PR 27; the
            # parent: 8.0, the view twice)
            _assert_pool_carried_and_no_view(compiled, pool_shards, 0.81)


@pytest.mark.slow
def test_tp4_train_step_compiles_and_is_split_four_ways(topo):
    one = _fits(_train_step_compiled(topo, 1, tp=1, sp=False))
    mesh_lib.destroy_model_parallel()
    compiled = _train_step_compiled(topo, 4, tp=4, sp=True)
    text = compiled.as_text()
    assert _kernels(text) and any(op.startswith("all-reduce") for op, _ in _ops(text))
    # per-device bytes: a 4-way split state is well under half the solo one
    assert _fits(compiled) < one / 2


@pytest.mark.slow
def test_tp4_engine_programs_compile(topo):
    _, lower_decode, lower_prefill, pool_shards = _engine_programs(topo, 4)
    for lower in (lower_decode, lower_prefill):
        compiled = lower().compile()
        assert _kernels(compiled.as_text())
        _fits(compiled)
        if lower is lower_decode:
            # per device: 0.42 GiB beside a view of 1
            _assert_pool_carried_and_no_view(compiled, pool_shards, 0.42)


def _deepseek_v2_lite(layers, seq):
    from neuronx_distributed_tpu.models.deepseek_v2 import (
        DeepseekV2ForCausalLM,
        deepseek_v2_lite,
    )

    return DeepseekV2ForCausalLM(
        deepseek_v2_lite(
            num_layers=layers, max_seq_len=seq, param_dtype=jnp.bfloat16,
            expert_strategy="blockwise",
        ),
        attention_impl="auto",
    )


@pytest.mark.slow
def test_deepseek_v2_lite_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    deepseek-v2-lite-serve.json``: its depth, 8 slots of 32,768, page 16):
    the fused decode chunk with the LATENT pool
    carried and the longest prompt's prefill, both with Pallas kernels and
    inside the chip's memory; the decode program holds no logical view of
    the latent cache, and its temporaries are the weights' layout copies
    (0.68 GiB beside a view of 2.0; the parent: 4.40)."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "deepseek-v2-lite-serve.json")) as f:
        config = json.load(f)
    seq = int(config["serving"]["max_seq_len"])
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=int(config["serving"]["num_slots"]), seq=seq,
        bucket=20992, model=_deepseek_v2_lite(int(config["model"]["num_hidden_layers"]), seq),
    )
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_latent_fused",
        "paged_attention": "fused", "moe_decode": "stream",
    }
    assert sorted(set(pool_shards)) == [(16385, 16, 1, 64), (16385, 16, 1, 512)]
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    assert _fits(decode, 15 * 1024**3)
    _assert_pool_carried_and_no_view(decode, pool_shards, 0.68)
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    assert _fits(prefill, 15 * 1024**3)
    _assert_head_on_one_row(prefill, 20992, int(config["model"]["vocab_size"]), 2.34)


def _assert_head_on_one_row(compiled, padded, vocab, temp_gib):
    """PR 49: a prefill applies its head to the last position alone, so its
    program holds no array of ``padded x vocab`` elements (the parent held
    ``bf16[1,20992,102400]``, 4.0 GiB, and its bitcast), and its temporaries
    are what the described-v5e compile showed (``temp_gib``; the parent: 4.10)
    and a tenth."""
    logits = sorted({
        made.group(0) for made in re.finditer(r"(?:bf16|f32)\[([\d,]+)\]", compiled.as_text())
        if math.prod(map(int, made.group(1).split(","))) >= padded * vocab
    })
    assert not logits, f"the prefill program computes logits at every position: {logits}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"prefill[{padded}]: temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * temp_gib * 2**30, (
        f"{temp / 2**30:.2f} GiB of temporaries, {temp_gib} when this bound was taken"
    )


def _keye_vl2(layers, seq):
    from neuronx_distributed_tpu.models.keye_vl2 import KeyeVL2ForCausalLM, keye_vl2_30b_a3b

    return KeyeVL2ForCausalLM(
        keye_vl2_30b_a3b(
            num_layers=layers, max_seq_len=seq, param_dtype=jnp.bfloat16,
            expert_strategy="blockwise",
        ),
        attention_impl="auto",
    )


# what the described-v5e compile of the configured depth showed for the decode
# chunk's temporaries (GiB): the 64-wide index-key leaf's layout conversions
# and the weights' hoisted layout copies (PR 30: 0.47 with K and V as two
# leaves; PR 31: 0.468 with the joined leaf, of which no copy at all)
KEYE_DECODE_TEMP_GIB = 0.47


@pytest.mark.slow
def test_keye_vl2_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    keye-vl2-30b-a3b-serve.json``: its depth, 8 slots of 32,768, page 16):
    the fused decode chunk with the two-leaf pool (K and V joined, the index
    keys) carried and the longest
    prompt's prefill under the byte mask, both with Pallas kernels and inside
    the chip's memory; the decode program holds no row-sized K/V array and
    its measured temporaries + 10%."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "keye-vl2-30b-a3b-serve.json")) as f:
        config = json.load(f)
    seq = int(config["serving"]["max_seq_len"])
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=int(config["serving"]["num_slots"]), seq=seq,
        bucket=24576, model=_keye_vl2(int(config["model"]["num_hidden_layers"]), seq),
    )
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_sparse_fused",
        "paged_attention": "fused", "moe_decode": "stream",
    }
    assert sorted(set(pool_shards)) == [(16385, 16, 1, 64), (16385, 16, 8, 128)]
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    assert _fits(decode, 15 * 1024**3)
    # no pool-sized copy of the joined leaf inside the decode scan: its
    # users there are two kernels of one layout
    _assert_pool_carried_and_no_view(decode, pool_shards, KEYE_DECODE_TEMP_GIB)
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    assert _fits(prefill, 15 * 1024**3)
    # no S x S array wider than a byte
    wide = [m.group(0) for m in re.finditer(r"(f32|bf16|s32|u32)\[[\d,]*24576,24576\]", prefill.as_text())]
    assert not wide, wide[:3]


def _glm5(cfg, seq):
    """The benchmark configuration's model, from its file's ``model`` group
    (the family file maps the published keys and the share's own)."""
    from perfbench.families import glm_moe_dsa

    return glm_moe_dsa.build(cfg, runner="serve", max_seq_len=seq)


# what the described-v5e compile of the configured depth showed for the two
# programs' temporaries (GiB; PR 32, the configuration's ``reduced_why``)
GLM5_DECODE_TEMP_GIB = 0.94
GLM5_PREFILL_TEMP_GIB = 4.46


def test_sparse_latent_decode_kernel_compiles_at_glm5_geometry(topo):
    """GLM-5's decode under its indexer at the serve cell's shapes (8 slots of
    32,768 columns, page 16, 64 heads against ONE latent row of 512 + 64 a
    token, 32 index heads of 128, 2048 kept): the index-score kernel over a
    leaf of 128 lanes (whole tiles: no padded copy) and the sparse latent
    kernel that fetches a token's joined (8, 128) tile with one copy. Neither
    may copy its pool leaf whole. Five rows instead of eight are refused:
    Mosaic copies whole HBM tiles, which is why the leaf has eight."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_index_scores,
        paged_sparse_latent_decode_attention,
    )

    s = _one_chip(topo)
    b, n_log, page, keep, heads = 8, 2048, 16, 2048, 64
    pages = b * n_log + 1
    table, valid = s((b, n_log), jnp.int32), s((b, n_log * page), jnp.bool_)
    text = _compiled_text(
        lambda q, w, pool, bt, pos, ok: paged_index_scores(q, w, pool, bt, pos, ok, page_size=page),
        s((b, 1, 32, 128)), s((b, 1, 32)), s((pages, page, 1, 128)), table, s((1,), jnp.int32), valid)
    assert _kernels(text)
    assert not re.search(r"bf16\[%d,%d,1,128\]\S* copy\(" % (pages, page), text), "the index-key leaf is copied whole"

    def attend(rows):
        return _compiled_text(
            lambda qc, qr, kv, bt, cols, n: paged_sparse_latent_decode_attention(
                qc, qr, kv, bt, cols, n, scale=0.0625, page_size=page),
            s((b, 1, heads, 512)), s((b, 1, heads, 64)), s((pages, page, rows, 128)), table,
            s((b, keep), jnp.int32), s((b,), jnp.int32))

    text = attend(8)
    assert _kernels(text)
    assert not re.search(r"bf16\[%d,%d,8,128\]\S* copy\(" % (pages, page), text), "the latent pool leaf is copied whole"
    with pytest.raises(Exception, match="aligned to tiling"):
        attend(5)


@pytest.mark.slow
def test_glm5_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    glm-5-serve.json``: its depth, its 8 held experts and vocabulary slice, 8
    slots of 32,768, page 16): the fused decode chunk with the two-leaf pool
    (the joined latent tile, the index keys) carried, and the longest
    prompt's prefill under the byte mask with the held experts' sorted loop,
    both with Pallas kernels and inside the chip's memory. The decode program
    holds no row-sized array and converts no pool leaf's layout inside its
    scan (PR 30's 2.7 GiB lesson); both programs' temporaries are what the
    configuration's ``reduced_why`` states + 10%; the prefill builds no
    ``tokens x top_k x hidden`` dispatch buffer."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "glm-5-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", "agentdocs_closed.json")) as f:
        longest = int(json.load(f)["prompt_len"]["max"])
    seq = int(config["serving"]["max_seq_len"])
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=int(config["serving"]["num_slots"]), seq=seq,
        bucket=longest, model=_glm5(config["model"], seq),
    )
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_sparse_latent_fused",
        "paged_attention": "fused", "moe_decode": "held",
    }
    assert sorted(set(pool_shards)) == [(16385, 16, 1, 128), (16385, 16, 8, 128)]
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    assert _fits(decode, 15 * 1024**3)
    # as _assert_pool_carried_and_no_view, but a view is looked for at the
    # JOINED leaf's size alone: the index keys' logical view holds as many
    # values (8 x 32,768 x 128) as W_q_b (2048 x 16,384), which IS there
    text = decode.as_text()
    shapes = {"bf16[%s]" % ",".join(map(str, s)) for s in pool_shards}
    copies = _copies_inside_loops(text, shapes)
    assert not copies, f"{len(copies)} whole-pool copies per decode step: " + "; ".join(copies[:3])
    assert not [ln for ln in text.splitlines() if " copy(" in ln and "bf16[16385,16," in ln], "a pool leaf is copied"
    views = _arrays_of_a_views_size(text, [(16385, 16, 8, 128)])
    assert not views, f"the decode program builds a logical latent view: {views}"
    temp = decode.memory_analysis().temp_size_in_bytes
    assert temp < 1.1 * GLM5_DECODE_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of decode temporaries"
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    assert _fits(prefill, 15 * 1024**3)
    temp = prefill.memory_analysis().temp_size_in_bytes
    assert temp < 1.1 * GLM5_PREFILL_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of prefill temporaries"
    text = prefill.as_text()
    # no S x S array of scores or keys (4 bytes a pair; a bf16 array of that
    # shape is q itself: 64 heads x 256 = 16,384 = the longest prompt), no
    # tokens x top_k x hidden gather
    wide = [m.group(0) for m in re.finditer(r"(f32|s32|u32)\[[\d,]*%d,%d\]" % (longest, longest), text)]
    assert not wide, wide[:3]
    hidden = int(config["model"]["hidden_size"])
    slots_rows = longest * int(config["model"]["num_experts_per_tok"])
    assert not re.search(r"bf16\[%d,%d\]" % (slots_rows, hidden), text), "a T x k x H dispatch buffer"


# --- Trinity-Large-Preview: window and full attention layers in one paged cache ------


def _trinity(cfg, seq):
    from perfbench.families import afmoe

    return afmoe.build(cfg, runner="serve", max_seq_len=seq)


# what the described-v5e compile of the configured depth showed for the two
# programs' temporaries (GiB; PR 39, the configuration's ``reduced_why``)
TRINITY_DECODE_TEMP_GIB = 0.40
TRINITY_PREFILL_TEMP_GIB = 1.41


@pytest.mark.parametrize("heads", [48, 32], ids=["trinity", "mixtral"])
@pytest.mark.parametrize("kind", ["window", "full"])
def test_walking_decode_kernel_compiles_at_trinity_geometry(topo, kind, heads):
    """Trinity's decode attention at the serve cell's shapes (8 slots of
    32,768 columns, page 16, 48 query heads against 8 kv heads of 128, K and V
    one joined leaf of (16, 128) a token): the kernel that walks the blocks a
    slot maps, over the full kind's pool and over the window kind's (272
    pages a slot), and the window pages' copies into it. Neither may copy
    its pool leaf whole. Mixtral's 32 query heads against the same leaf
    likewise: the geometry cell 2 brings to this kernel (CodeGen2's 16 kv
    heads of 256 do not fit two blocks of 512 tokens into the scoped VMEM:
    ROADMAP queue 1 item 2)."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_scatter_window_pages_dma,
        paged_walk_decode_attention,
    )

    s = _one_chip(topo)
    b, n_log, page = 8, 2048, 16
    pages = b * (n_log if kind == "full" else 272) + 1
    table, valid = s((b, n_log), jnp.int32), s((b, n_log * page), jnp.bool_)
    floor = s((b,), jnp.int32) if kind == "window" else None

    def step(q, pool, win, bt, pos, ok, lo):
        pool = paged_scatter_window_pages_dma(pool, win, bt, pos[0] // page)
        return paged_walk_decode_attention(q, pool, bt, pos, kv_valid=ok, floor=lo, page_size=page), pool

    # the pool is donated, as the decode scan's carry is: the copies' kernel writes it in place
    text = jax.jit(step, donate_argnums=(1,)).lower(
        s((b, 1, heads, 128)), s((pages, page, 16, 128)), s((b, 2 * page, 16, 128)), table,
        s((1,), jnp.int32), valid, floor).compile().as_text()
    assert _kernels(text) >= 2
    assert not re.search(r"bf16\[%d,%d,16,128\]\S* copy\(" % (pages, page), text), "the joined pool leaf is copied whole"


@pytest.mark.parametrize("seq", [4096, 16384])
@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_banded_flash_prefill_compiles_at_trinity_geometry(topo, seq, window):
    """A window layer's banded forward and a full layer's flash forward (the
    padding mask as segment -1), as ``AfmoeAttention`` calls them."""
    from neuronx_distributed_tpu.modules.attention import window_prefill_attention

    s = _one_chip(topo)
    text = _compiled_text(
        lambda q, k, v, ok: window_prefill_attention(q, k, v, window, impl="flash", mask=ok),
        s((1, seq, 48, 128)), s((1, seq, 8, 128)), s((1, seq, 8, 128)), s((1, seq), jnp.bool_))
    assert _kernels(text)


# The grouped prefill forwards (PR 50) at cells 5, 6 and 7's head geometry and
# window (``chip_smoke.GROUP_BUNDLES``, by phase): the buckets, and the
# instruction bundles of ONE step's body in PR 49's kernels at the 16,384 bucket
# (a KV head's group on a 512 x 512 tile; ``tests/kernels/_group_fwd_parent.py``
# through ``chip_smoke.group_body_bundles(..., parent=True)`` on this
# installation: the frozen kernels do not change, so neither do these)
_GROUPED_CELLS = {
    "keye_longdocs_closed": ("dsa", (16384, 32768), 15177),
    "glm5_agentdocs_closed": ("glm", (16384,), 2543),
    "trinity_mixedctx_closed": ("trinity", (16384,), 11672),
}


@pytest.mark.parametrize("cell", sorted(_GROUPED_CELLS))
def test_grouped_prefill_forward_compiles_at_the_cells_geometry(topo, cell, tmp_path):
    """The byte-masked and the banded forward in the flash forward's form: each
    compiles inside the scoped VMEM limit the compiler grants (no
    ``vmem_limit_bytes`` asked: Keye's group of 8 tiles its queries by 256 for
    it), its grid is the list of the triangle's or the band's pairs with the
    plan a (batch row, pair) table, and a step's bodies hold fewer instruction
    bundles a query row than PR 49's one body did."""
    from neuronx_distributed_tpu.kernels.flash_attention import (
        _group_blocks,
        banded_flash_attention,
        group_tile_plan,
        masked_flash_attention,
    )

    import chip_smoke

    phase, buckets, parents_body = _GROUPED_CELLS[cell]
    heads, window = chip_smoke.GROUP_BUNDLES[phase]
    h, hkv, d, dv = heads
    s = _one_chip(topo)
    for seq in buckets:
        qkv = (s((1, seq, h, d)), s((1, seq, hkv, d)), s((1, seq, hkv, dv)))
        if window is None:
            text = _compiled_text(lambda q, k, v, m, ok: masked_flash_attention(q, k, v, m, ok),
                                  *qkv, s((1, seq, seq), jnp.int8), s((1, seq), jnp.bool_))
        else:
            text = _compiled_text(lambda q, k, v, ok: banded_flash_attention(q, k, v, window, ok),
                                  *qkv, s((1, seq), jnp.bool_))
        steps = group_tile_plan(seq, seq, h // hkv, window)[0]
        assert _kernels(text) and f"s32[1,{steps}]" in text
    bq = _group_blocks(buckets[0], h // hkv)[0]
    bodies = chip_smoke.group_body_bundles(str(tmp_path), heads, window, buckets[0])
    assert len(bodies) == (1 if window is None else 2)
    assert all(0 < body * (512 // bq) < 0.85 * parents_body for body in bodies), (bodies, bq)


@pytest.mark.slow
def test_trinity_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    trinity-large-serve.json``: its depth, its 32 held experts and vocabulary
    slice, 8 slots of 32,768, page 16): the fused decode chunk with BOTH
    kinds' pools carried (the full kind's rows, the window kind's 272 pages a
    slot) and the longest prompt's prefill through the banded flash forward
    (window layers) and the flash forward (the full layer) with the held
    experts' sorted loop, both with Pallas kernels and inside
    the chip's memory. The decode program holds no row-sized array and copies
    no pool leaf; both programs' temporaries are what the configuration's
    ``reduced_why`` states + 10%."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "trinity-large-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", "mixedctx_closed.json")) as f:
        longest = int(json.load(f)["prompt_len"]["max"])
    seq = int(config["serving"]["max_seq_len"])
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=int(config["serving"]["num_slots"]), seq=seq,
        bucket=longest, model=_trinity(config["model"], seq),
    )
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_walk_fused",
        "paged_attention": "fused", "moe_decode": "held",
    }
    # one full layer's rows, four window layers' 8 x 272 pages (+ the null page each)
    assert sorted(pool_shards) == [(2177, 16, 16, 128)] * 4 + [(16385, 16, 16, 128)]
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    live = _fits(decode, 15 * 1024**3)
    text = decode.as_text()
    shapes = {"bf16[%s]" % ",".join(map(str, s)) for s in pool_shards}
    copies = _copies_inside_loops(text, shapes)
    assert not copies, f"{len(copies)} whole-pool copies per decode step: " + "; ".join(copies[:3])
    assert not [ln for ln in text.splitlines() if " copy(" in ln and re.search(r"bf16\[(16385|2177),16,16,128\]", ln)], \
        "a pool leaf is copied"
    views = _arrays_of_a_views_size(text, [(16385, 16, 16, 128)])
    assert not views, f"the decode program builds a logical K/V view: {views}"
    temp = decode.memory_analysis().temp_size_in_bytes
    print(f"trinity decode: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * TRINITY_DECODE_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of decode temporaries"
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    live = _fits(prefill, 15 * 1024**3)
    temp = prefill.memory_analysis().temp_size_in_bytes
    print(f"trinity prefill[{longest}]: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * TRINITY_PREFILL_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of prefill temporaries"
    text = prefill.as_text()
    wide = [m.group(0) for m in re.finditer(r"(f32|s32|u32|pred|s8)\[[\d,]*%d,%d\]" % (longest, longest), text)]
    assert not wide, wide[:3]


# --- ZAYA1-8B: a joined leaf of (4, 128) a token and a per-slot state ----------------

# what the described-v5e compile of the configured depth showed for the two
# programs' temporaries (GiB; PR 47, the configuration's ``reduced_why``)
ZAYA_DECODE_TEMP_GIB = 0.10
ZAYA_PREFILL_TEMP_GIB = 0.39


def test_walking_decode_kernel_compiles_on_a_quarter_tile_leaf_and_the_pool_stays_dense(topo):
    """ZAYA1-8B's decode attention at the serve cell's shapes (32 slots of
    16,384 columns, page 16, 8 query heads against 2 kv heads of 128, K and V
    one joined leaf of (4, 128) a token: a QUARTER of a bf16 tile): the kernel
    that walks the blocks a slot maps reads the fetched block through its
    32-bit view (two words a token) as it is, the window pages' copies go into
    the pool in place, and the compiler holds the pool leaf DENSE, tiled (4,
    128): 1,024 B a token, not a (16, 128) tile's 4,096."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_scatter_window_pages_dma,
        paged_walk_decode_attention,
    )

    s = _one_chip(topo)
    b, n_log, page = 32, 1024, 16
    pages = b * n_log + 1

    def step(q, pool, win, bt, pos, ok):
        pool = paged_scatter_window_pages_dma(pool, win, bt, pos[0] // page)
        return paged_walk_decode_attention(q, pool, bt, pos, kv_valid=ok, page_size=page), pool

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        s((b, 1, 8, 128)), s((pages, page, 4, 128)), s((b, 2 * page, 4, 128)), s((b, n_log), jnp.int32),
        s((1,), jnp.int32), s((b, n_log * page), jnp.bool_)).compile()
    text = compiled.as_text()
    assert _kernels(text) >= 2
    assert not re.search(r"bf16\[%d,%d,4,128\]\S* copy\(" % (pages, page), text), "the joined pool leaf is copied whole"
    assert re.search(r"bf16\[%d,%d,4,128\]\{3,2,1,0:T\(4,128\)\(2,1\)\}" % (pages, page), text), "the pool leaf's tiling"
    dense = pages * page * 4 * 128 * 2
    held = compiled.memory_analysis().argument_size_in_bytes
    assert dense <= held < dense + 2**24, f"{held} bytes of arguments for a dense pool of {dense}"


def _zaya(cfg, seq):
    from perfbench.families import zaya

    return zaya.build(cfg, runner="serve", max_seq_len=seq)


@pytest.mark.slow
def test_zaya_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    zaya1-8b-serve.json``: its depth, all 16 experts, the whole vocabulary, 32
    slots of 16,384, page 16): the fused decode chunk with every layer's pool
    AND per-slot state carried, and the longest prompt's prefill, both with
    Pallas kernels and inside the chip's memory. The decode program holds no
    row-sized array and copies no pool leaf; both programs' temporaries are
    what the configuration's ``reduced_why`` states + 10%."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "zaya1-8b-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", "reasoning_closed.json")) as f:
        longest = int(json.load(f)["prompt_len"]["max"])
    seq, slots = int(config["serving"]["max_seq_len"]), int(config["serving"]["num_slots"])
    model = _zaya(config["model"], seq)
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=slots, seq=seq, bucket=longest, model=model)
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_walk_fused",
        "paged_attention": "fused", "moe_decode": "stream",
    }
    layers = int(config["model"]["num_hidden_layers"])
    assert pool_shards == [(slots * seq // 16 + 1, 16, 4, 128)] * layers
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    live = _fits(decode, 15 * 1024**3)
    text = decode.as_text()
    copies = _copies_inside_loops(text, {"bf16[%s]" % ",".join(map(str, pool_shards[0]))})
    assert not copies, f"{len(copies)} whole-pool copies per decode step: " + "; ".join(copies[:3])
    assert not _arrays_of_a_views_size(text, pool_shards[:1])
    temp = decode.memory_analysis().temp_size_in_bytes
    print(f"zaya decode: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * ZAYA_DECODE_TEMP_GIB * 2**30 + 2**26, f"{temp / 2**30:.2f} GiB of decode temporaries"
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    live = _fits(prefill, 15 * 1024**3)
    temp = prefill.memory_analysis().temp_size_in_bytes
    print(f"zaya prefill[{longest}]: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * ZAYA_PREFILL_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of prefill temporaries"


# --- Solar-Open2-250B: recurrent layers whose state lives per slot, beside paged GQA ---

# what the described-v5e compile of the configured depth showed for the two
# programs' temporaries (GiB; PR 51, the configuration's ``reduced_why``)
SOLAR_DECODE_TEMP_GIB = 0.23
SOLAR_PREFILL_TEMP_GIB = 1.79
SOLAR_STATE = (64, 128, 128)      # a slot's float32 state a linear layer: 4 MiB


def test_delta_rule_kernels_compile_at_solar_geometry(topo):
    """Both recurrences at the serve cell's shapes (64 heads of 128; 16 slots;
    an 8,192-token prompt in chunks of 128): Mosaic takes them, the decode
    step's state result IS its state operand (no second array of its size,
    no temporary at all), and the prefill holds a chunk's rows and nothing a
    prompt long."""
    from neuronx_distributed_tpu.kernels.delta_rule import kda_chunk_prefill, kda_decode_step

    s = _one_chip(topo)
    f32 = jnp.float32
    decode = jax.jit(kda_decode_step, donate_argnums=0).lower(
        s((16,) + SOLAR_STATE, f32), s((16, 64, 128)), s((16, 64, 128)), s((16, 64, 128)),
        s((16, 64, 128), f32), s((16, 64), f32)).compile()
    assert _kernels(decode.as_text()) == 1
    m = decode.memory_analysis()
    state = 16 * math.prod(SOLAR_STATE) * 4
    assert m.alias_size_in_bytes == state and m.temp_size_in_bytes < state // 16
    prefill = jax.jit(kda_chunk_prefill).lower(
        s((1, 8192, 64, 128)), s((1, 8192, 64, 128)), s((1, 8192, 64, 128)), s((1, 8192, 64, 128), f32),
        s((1, 8192, 64), f32), s((1, 8192), jnp.bool_)).compile()
    assert _kernels(prefill.as_text()) == 1


def _solar(cfg, seq):
    from perfbench.families import solar_open2

    return solar_open2.build(cfg, runner="serve", max_seq_len=seq)


@pytest.mark.slow
def test_solar_open2_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    solar-open2-250b-serve.json``: 8 layers, 10 of 320 experts held, the
    vocabulary's slice, 16 slots of 32,768, page 16): the fused decode chunk
    with the two GQA layers' pools AND the six linear layers' per-slot state
    carried, and the longest prompt's prefill, both with Pallas kernels and
    inside the chip's memory. The decode program copies no pool leaf, holds no
    row-sized array and NO ARRAY OF THE STATE'S SIZE among its temporaries
    (the state goes through the chunk's scan and the kernel in place)."""
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "solar-open2-250b-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", "analysis_closed.json")) as f:
        longest = int(json.load(f)["prompt_len"]["max"])
    seq, slots = int(config["serving"]["max_seq_len"]), int(config["serving"]["num_slots"])
    model = _solar(config["model"], seq)
    engine, lower_decode, lower_prefill, shards = _engine_programs(
        topo, 1, slots=slots, seq=seq, bucket=longest, model=model)
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_walk_fused",
        "paged_attention": "fused", "moe_decode": "held",
    }
    state = (slots,) + SOLAR_STATE
    assert shards.count(state) == 6 and [s for s in shards if s != state] == [(slots * seq // 16 + 1, 16, 16, 128)] * 2
    decode = lower_decode().compile()
    assert _kernels(decode.as_text())
    live = _fits(decode, 15 * 1024**3)
    text = decode.as_text()
    pool = "bf16[%s]" % ",".join(map(str, (slots * seq // 16 + 1, 16, 16, 128)))
    held = "f32[%s]" % ",".join(map(str, state))
    copies = _copies_inside_loops(text, {pool, held})
    assert not copies, f"{len(copies)} whole-leaf copies per decode step: " + "; ".join(copies[:3])
    assert not _arrays_of_a_views_size(text, [(slots * seq // 16 + 1, 16, 16, 128)])
    temp = decode.memory_analysis().temp_size_in_bytes
    print(f"solar decode: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * SOLAR_DECODE_TEMP_GIB * 2**30 + 2**26, f"{temp / 2**30:.2f} GiB of decode temporaries"
    prefill = lower_prefill().compile()
    assert _kernels(prefill.as_text())
    live = _fits(prefill, 15 * 1024**3)
    temp = prefill.memory_analysis().temp_size_in_bytes
    print(f"solar prefill[{longest}]: live {live / 2**30:.2f} GiB, temporaries {temp / 2**30:.2f} GiB")
    assert temp < 1.1 * SOLAR_PREFILL_TEMP_GIB * 2**30, f"{temp / 2**30:.2f} GiB of prefill temporaries"


# --- Ouro-2.6B: a stack run four times over one set of weights, a K/V cache a pass --


def _ouro(cfg, seq):
    from perfbench.families import ouro

    return ouro.build(cfg, runner="serve", max_seq_len=seq)


def test_walking_decode_kernel_compiles_at_ouro_geometry(topo):
    """Ouro's decode attention at the serve cell's shapes (2 slots of 6,144
    columns, page 16, 16 query heads against 16 kv heads of 128: MHA, K and V
    one joined leaf of (32, 128) a token, 8 KiB): the kernel that walks the
    blocks a slot maps, in its form for one query row a kv head (the block
    multiplied as it lies: 128 tokens a block, 2,048 score columns; by a
    token's bytes alone it would be 256, three blocks of 2 MiB where 512
    tokens would hold 12 MiB of the scoped VMEM), and the window pages'
    copies into the pool. Neither may copy its pool leaf
    whole. Trinity's and ZAYA1's leaves keep 512 tokens a block."""
    from neuronx_distributed_tpu.kernels.flash_decode import (
        paged_scatter_window_pages_dma,
        paged_walk_decode_attention,
        walk_block_tokens,
    )

    assert walk_block_tokens(32 * 128 * 2, 16) == 256 and walk_block_tokens(32 * 128 * 2, 16, row_heads=16) == 128
    assert walk_block_tokens(16 * 128 * 2, 16) == walk_block_tokens(4 * 128 * 2, 16) == 512
    s = _one_chip(topo)
    b, n_log, page = 2, 384, 16
    pages = b * n_log + 1
    table, valid = s((b, n_log), jnp.int32), s((b, n_log * page), jnp.bool_)

    def step(q, pool, win, bt, pos, ok):
        pool = paged_scatter_window_pages_dma(pool, win, bt, pos[0] // page)
        return paged_walk_decode_attention(q, pool, bt, pos, kv_valid=ok, page_size=page), pool

    text = jax.jit(step, donate_argnums=(1,)).lower(
        s((b, 1, 16, 128)), s((pages, page, 32, 128)), s((b, 2 * page, 32, 128)), table,
        s((1,), jnp.int32), valid).compile().as_text()
    assert _kernels(text) >= 2
    assert not re.search(r"bf16\[%d,%d,32,128\]\S* copy\(" % (pages, page), text), "the joined pool leaf is copied whole"


@pytest.mark.slow
def test_ouro_engine_programs_compile_and_fit(topo):
    """The benchmark configuration's programs (``perfbench/configs/
    ouro-2.6b-serve.json``: its layers x 4 passes, its slots and rows, page
    16): the fused decode chunk with every cache node's pool carried (one a
    layer a pass, one block table) and the longest prompt's prefill through the
    flash forward, both with Pallas kernels and inside the chip's memory: a
    layer body a node unrolled in the chunk, the prefill ROLLED over the passes
    (a layer body a layer) and its output a row of the BUCKET's columns. The
    decode program holds no row-sized array and copies no pool leaf; the
    weights are held ONCE (the arguments are the pool and one stack's weights,
    not four)."""
    import json
    import time

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "perfbench", "configs", "ouro-2.6b-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic", "worked_closed.json")) as f:
        longest = int(json.load(f)["prompt_len"]["max"])
    seq, slots = int(config["serving"]["max_seq_len"]), int(config["serving"]["num_slots"])
    layers, passes = int(config["model"]["num_hidden_layers"]), int(config["model"]["total_ut_steps"])
    engine, lower_decode, lower_prefill, pool_shards = _engine_programs(
        topo, 1, slots=slots, seq=seq, bucket=longest, model=_ouro(config["model"], seq))
    assert engine.programs.resolved == {
        "attention": "flash", "decode_attention": "paged_walk_fused", "paged_attention": "fused"}
    leaf = (slots * seq // 16 + 1, 16, 32, 128)
    assert pool_shards == [leaf] * (layers * passes)
    t0 = time.perf_counter()
    lowered = lower_decode()
    t_lower = time.perf_counter() - t0
    decode = lowered.compile()
    t_decode = time.perf_counter() - t0
    text = decode.as_text()
    assert _kernels(text) >= 2 * layers * passes            # a walk and a window copy a node
    live = _fits(decode, 15 * 1024**3)
    m = decode.memory_analysis()
    beside = m.argument_size_in_bytes - layers * passes * math.prod(leaf) * 2
    g = config["model"]
    held = 2 * (layers * (4 * g["hidden_size"] ** 2 + 3 * g["hidden_size"] * g["intermediate_size"])
                + 2 * g["vocab_size"] * g["hidden_size"])
    assert held < beside < 1.02 * held, (
        f"{beside / 1e9:.2f} GB of arguments beside the pool, {held / 1e9:.2f} GB of weights: the stack is held once")
    shape = "bf16[%s]" % ",".join(map(str, leaf))
    copies = _copies_inside_loops(text, {shape})
    assert not copies, f"{len(copies)} whole-pool copies per decode step: " + "; ".join(copies[:3])
    assert not _arrays_of_a_views_size(text, [leaf])
    print(f"ouro decode: traced and lowered in {t_lower:.0f} s (every process pays that), compiled in {t_decode:.0f} s, live {live / 2**30:.2f} GiB, "
          f"temporaries {m.temp_size_in_bytes / 2**30:.2f} GiB")
    t0 = time.perf_counter()
    prefill = lower_prefill().compile()
    t_prefill = time.perf_counter() - t0
    assert layers <= _kernels(prefill.as_text()) < 2 * layers              # the passes are one loop
    live = _fits(prefill, 15 * 1024**3)
    row = layers * passes * longest * 32 * 128 * 2                          # the bucket's columns of every node
    assert row <= prefill.memory_analysis().output_size_in_bytes < 1.01 * row + 2**20
    print(f"ouro prefill[{longest}]: compiled in {t_prefill:.0f} s, live {live / 2**30:.2f} GiB, "
          f"temporaries {prefill.memory_analysis().temp_size_in_bytes / 2**30:.2f} GiB")
