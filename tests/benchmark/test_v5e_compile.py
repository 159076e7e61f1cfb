"""The CodeGen2 cells' attention kernels at their head geometry, compiled for
a DESCRIBED v5e from this CPU process. The program's own compile tests
(``tests/kernels/test_tpu_compile.py``) cover heads of 128 only; both CodeGen2
configurations run 16 heads of 256 (an ``assumed`` width, PERF.md §7), and
what Mosaic refuses at that size must fail here and not on the chip. Nothing
runs, so nothing here says anything about results or speed."""

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neuronx_distributed_tpu.kernels import backend
from neuronx_distributed_tpu.kernels.flash_attention import flash_attention
from neuronx_distributed_tpu.kernels.flash_decode import paged_flash_decode_attention

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KERNEL = "tpu_custom_call"


def _config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    """Abstract arrays on one chip of a described (not attached) v5e, with
    kernels uninterpreted and the persistent cache off, as the program's own
    compile tests set them; skip where libtpu cannot describe the topology."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    sharding = SingleDeviceSharding(topo.devices[0])
    patch = pytest.MonkeyPatch()
    patch.setattr(backend, "INTERPRET", False)
    patch.setattr(backend, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    patch.undo()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_fwd_bwd_compiles_at_the_train_cells_heads(spec):
    model = _config("codegen2-7b-train-tp4")["model"]
    heads, d = model["n_head"], model["n_embd"] // model["n_head"]
    q = spec((2, model["n_positions"], heads // 4, d))          # one chip's heads under tp=4

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    assert (heads, d) == (16, 256)
    assert KERNEL in _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_flash_prefill_and_paged_decode_compile_at_the_serve_cells_heads(spec):
    config = _config("codegen2-7b-serve")
    model, serving = config["model"], config["serving"]
    heads, d = model["n_head"], model["n_embd"] // model["n_head"]
    slots, row, page = serving["num_slots"], serving["max_seq_len"], serving["kv_page_size"]
    q = spec((1, 2048, heads, d))                                # the largest prefill bucket
    assert KERNEL in _compiled_text(lambda q, k, v: flash_attention(q, k, v), q, q, q)

    q1, pool = spec((slots, 1, heads, d)), spec((slots * row // page, page, heads, d))
    table, pos = spec((slots, row // page), jnp.int32), spec((1,), jnp.int32)
    valid = spec((slots, row), jnp.bool_)

    def decode(q, k, v, table, pos, valid):
        return paged_flash_decode_attention(q, k, v, table, pos, valid, page_size=page)

    assert KERNEL in _compiled_text(decode, q1, pool, pool, table, pos, valid)
