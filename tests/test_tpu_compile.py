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
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compression import sbc
from repro.fed import feel_model
from repro.fed.model_engine import SEQ_CAP, family_arch
from repro.kernels import ops
from repro.models.mamba2 import dims as mamba2_dims

K_CLIENTS = 6      # the table-2 fleet
FIG45_LANES = (64, 6)  # the fig45 grid: 64 rows of 6 clients
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


def _named(text, name):
    """Whether some kernel call of the compiled text carries ``name`` (the
    ``pallas_call``'s ``name=``, which a profile shows for the call)."""
    return any(name in line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)


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
    assert _named(text, "flash_attention")


def test_flash_attention_grad_compiles(one_chip):
    step = jax.value_and_grad(lambda q, k, v: jnp.sum(_flash(q, k, v)),
                              argnums=(0, 1, 2))
    text = _compiled_text(step, _attention_shapes(), one_chip)
    assert _n_kernels(text) >= 1


def test_ssd_forward_compiles(one_chip):
    shapes, chunk = _ssd_shapes()
    fn = lambda *a: ops.ssd(*a, chunk=chunk, interpret=False)  # noqa: E731
    text = _compiled_text(fn, shapes, one_chip)
    assert _n_kernels(text) >= 1
    assert _named(text, "ssd_scan")


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
    assert _named(text, "sbc_stats") and _named(text, "sbc_apply")


def test_flash_decode_compiles_with_its_name(one_chip):
    """One query token against a 1,024-token cache, 4 query heads over 2
    kv heads of 64."""
    fn = lambda q, k, v: ops.flash_decode(  # noqa: E731
        q, k, v, 1000, interpret=False)
    text = _compiled_text(fn, [(2, 1, 4, 64), (2, 1024, 2, 64),
                               (2, 1024, 2, 64)], one_chip)
    assert _n_kernels(text) == 1
    assert _named(text, "flash_decode")


def _computations(text):
    """{name: lines} of every computation in compiled HLO text."""
    out, lines = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line[0].isspace():
            lines = out.setdefault(line.removeprefix("ENTRY ").split()[0], [])
        elif line == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    return out


def test_sbc_threshold_search_reads_each_leaf_once_a_pass(one_chip):
    """``compress_dense`` over the fig45 lanes (64 rows of 6 clients, as
    the engine nests them) at the MLP's leaf shapes (3072-256-256-10):
    one loop per leaf of ``count_passes()`` passes, and in each pass one
    fusion that reads the leaf's magnitudes and counts against all
    ``2**_LEVELS - 1`` thresholds as sibling reductions."""
    params = jax.eval_shape(lambda: feel_model.init(
        jax.random.key(0), 256, depth=3, input_dim=3072))
    lanes = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(FIG45_LANES + p.shape, p.dtype,
                                       sharding=one_chip), params)
    step = jax.vmap(jax.vmap(lambda g, r: sbc.compress_dense(g, 0.005, r)))
    text = jax.jit(step).lower(lanes, lanes).compile().as_text()
    comps = _computations(text)
    loops = [line for line in text.splitlines() if " while(" in line]
    # each loop carries its leaf's magnitudes, in a (rows, 128) slab where
    # that fills whole (8, 128) tiles: the six clients never meet the tile
    lead = ",".join(map(str, FIG45_LANES))
    carried = [re.search(rf"f32\[{lead},([\d,]+)\]", loop)
               for loop in loops]
    dims = [[int(d) for d in m[1].split(",")] for m in carried]
    assert sorted(math.prod(d) for d in dims) == sorted(
        p.size for p in jax.tree_util.tree_leaves(params))
    assert [len(d) for d in dims] == [
        2 if math.prod(d) % (8 * 128) == 0 else 1 for d in dims]
    assert all(d[-1] == 128 for d in dims if len(d) == 2)
    for loop, leaf in zip(loops, (m[0] for m in carried)):
        magnitudes = re.compile(re.escape(leaf) + r"\S* parameter\(")
        cond = re.search(r"condition=(%[\w.\-]+)", loop)[1]
        body = re.search(r"body=(%[\w.\-]+)", loop)[1]
        assert any(f"constant({sbc.count_passes()})" in line
                   for line in comps[cond])
        reads = [line for line in comps[body] if " fusion(" in line
                 and any(magnitudes.search(p) for p in comps[
                     re.search(r"calls=(%[\w.\-]+)", line)[1]])]
        assert len(reads) == 1, reads
        outputs = reads[0].split(" fusion(")[0]
        assert outputs.count(f"s32[{lead}]") == 2 ** sbc._LEVELS - 1
