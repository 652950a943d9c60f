"""Compile the main-path Pallas kernels for one TPU v5e chip that is
described, not attached.

Nothing runs here: each test lowers a kernel entry point at the model
families' real shapes and compiles it with the TPU compiler, which refuses
what the chip would refuse (block tiling, VMEM stores, memory) and which
interpret mode cannot check.  ``interpret=False`` steers ``kernels.ops``
onto the kernel path although the backend is the CPU.  The topology is
described inside a fixture, never at import, because only one process at
a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fed.model_engine import SEQ_CAP, family_arch
from repro.kernels import ops
from repro.models.mamba2 import dims as mamba2_dims

K_CLIENTS = 6      # the table-2 fleet
SLOT = 128         # b_max: sequences per client batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _n_kernels(text):
    return text.count('custom_call_target="tpu_custom_call"')


def _attention_shapes():
    """hidden 256: 4 query heads over 2 kv heads of 64 → BH = 512."""
    cfg = family_arch("transformer", 256, 3)
    hd = cfg.d_model // cfg.n_heads
    return [(SLOT, SEQ_CAP, cfg.n_heads, hd),
            (SLOT, SEQ_CAP, cfg.n_kv_heads, hd),
            (SLOT, SEQ_CAP, cfg.n_kv_heads, hd)]


def _ssd_shapes():
    """hidden 256: 64 SSM heads of 8 channels, one 16-state group."""
    cfg = family_arch("mamba2", 256, 3)
    s = cfg.ssm
    _, H, _ = mamba2_dims(cfg)
    return ([(SLOT, SEQ_CAP, H, s.head_dim), (SLOT, SEQ_CAP, H), (H,),
             (SLOT, SEQ_CAP, s.n_groups, s.d_state),
             (SLOT, SEQ_CAP, s.n_groups, s.d_state)], s.chunk)


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, interpret=False)


def test_flash_attention_forward_compiles(one_chip):
    text = _compiled_text(_flash, _attention_shapes(), one_chip)
    assert _n_kernels(text) >= 1


def test_flash_attention_grad_compiles(one_chip):
    step = jax.value_and_grad(lambda q, k, v: jnp.sum(_flash(q, k, v)),
                              argnums=(0, 1, 2))
    text = _compiled_text(step, _attention_shapes(), one_chip)
    assert _n_kernels(text) >= 1


def test_ssd_forward_compiles(one_chip):
    shapes, chunk = _ssd_shapes()
    fn = lambda *a: ops.ssd(*a, chunk=chunk, interpret=False)  # noqa: E731
    assert _n_kernels(_compiled_text(fn, shapes, one_chip)) >= 1


def test_ssd_grad_compiles(one_chip):
    shapes, chunk = _ssd_shapes()
    step = jax.value_and_grad(
        lambda *a: jnp.sum(ops.ssd(*a, chunk=chunk, interpret=False)),
        argnums=tuple(range(5)))
    assert _n_kernels(_compiled_text(step, shapes, one_chip)) >= 1


@pytest.mark.parametrize("lead", [(K_CLIENTS,), (2, K_CLIENTS)],
                         ids=["clients", "rows-clients"])
def test_sbc_kernels_compile_under_client_vmap(one_chip, lead):
    """``sbc_stats`` + ``sbc_apply`` on a 256×512 leaf, vmapped as the
    engine runs them; the top-k threshold is an input here (XLA's top_k
    is not a kernel, and compiling it alone takes longer than both)."""
    fn = lambda g, thr: ops.sbc_binarize(g, thr)  # noqa: E731
    for _ in lead:
        fn = jax.vmap(fn)
    text = _compiled_text(fn, [lead + (256, 512), lead], one_chip)
    assert _n_kernels(text) == 2
