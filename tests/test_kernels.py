"""Per-kernel allclose vs the pure-jnp oracles: shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.kernels import ops, ref
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(42)


def _qkv(b, s, h, kv, d, dtype=jnp.float32):
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("b,s,h,kv,d,bq,bk", [
    (1, 128, 2, 2, 32, 64, 64),      # MHA
    (2, 256, 4, 2, 64, 128, 128),    # GQA rep=2
    (1, 192, 8, 1, 16, 64, 128),     # MQA, ragged seq vs blocks
    (1, 96, 2, 2, 64, 128, 128),     # seq < block (degenerate single block)
])
def test_flash_attention_shapes(b, s, h, kv, d, bq, bk):
    q, k, v = _qkv(b, s, h, kv, d)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_flash_attention_noncausal_and_window():
    q, k, v = _qkv(1, 160, 4, 4, 32)
    for kwargs in ({"causal": False}, {"causal": True, "window": 48}):
        out = ops.flash_attention(q, k, v, block_q=64, block_k=64, **kwargs)
        want = ref.flash_attention_ref(q, k, v, **kwargs)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5,
                                   err_msg=str(kwargs))


def test_flash_attention_bf16():
    q, k, v = _qkv(1, 128, 2, 2, 64, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=3e-2, rtol=3e-2)


def test_flash_attention_grad_matches_oracle():
    q, k, v = _qkv(1, 128, 2, 2, 32)

    def f_k(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, block_q=64, block_k=64) ** 2)

    def f_r(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v) ** 2)

    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 16, 2, 16, 32),
    (1, 100, 4, 8, 1, 8, 32),        # ragged: s % chunk != 0
])
def test_ssd_kernel_shapes(b, s, h, p, g, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32)) * 0.1
    Bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    y1, f1 = ops.ssd_chunked_pallas(x, dA, Bm, Cm, chunk=chunk)
    y2, f2 = ssd_chunked(x, dA, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(f1, f2, atol=2e-4, rtol=2e-4)


def test_ssd_intra_chunk_vs_einsum_ref():
    b, nc, q, h, p, g, n = 1, 3, 32, 4, 16, 2, 16
    x = jnp.asarray(RNG.normal(size=(b, nc, q, h, p)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, nc, q, h)), jnp.float32)) * 0.1
    Bm = jnp.asarray(RNG.normal(size=(b, nc, q, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, nc, q, g, n)), jnp.float32)
    from repro.kernels.ssd_scan import ssd_intra_chunk

    y1, s1 = ssd_intra_chunk(x, dA, Bm, Cm, interpret=True)
    y2, s2 = ref.ssd_intra_chunk_ref(x, dA, Bm, Cm)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s1, s2, atol=2e-4, rtol=2e-4)


def test_ssd_with_initial_state():
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32)) * 0.1
    Bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(b, h, n, p)), jnp.float32)
    y1, f1 = ops.ssd_chunked_pallas(x, dA, Bm, Cm, chunk=32, initial_state=s0)
    y2, f2 = ssd_chunked(x, dA, Bm, Cm, chunk=32, initial_state=s0)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(f1, f2, atol=2e-4, rtol=2e-4)


@settings(max_examples=20, deadline=None)
@given(
    t=st.integers(1, 12),
    rows=st.integers(1, 8),
    cols=st.sampled_from([8, 16, 128]),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_blocks_property(t, rows, cols, seed):
    rng = np.random.default_rng(seed)
    n_tiles_src = 16
    src = jnp.asarray(rng.normal(size=(n_tiles_src * rows, cols)), jnp.float32)
    offs = jnp.asarray(rng.integers(0, n_tiles_src, size=t), jnp.int32)
    got = ops.pack_blocks(src, offs, tile_rows=rows, block_cols=cols)
    want = ref.pack_blocks_ref(src, offs, tile_rows=rows)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_pack_blocks_dtypes(dtype):
    src = jnp.arange(64 * 8).reshape(64, 8).astype(dtype)
    offs = jnp.asarray([7, 0, 3], jnp.int32)
    got = ops.pack_blocks(src, offs, tile_rows=8, block_cols=8)
    want = ref.pack_blocks_ref(src, offs, tile_rows=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(
    t=st.integers(1, 12),
    rows=st.sampled_from([8, 16]),
    cols=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_pack_cols_property(t, rows, cols, seed):
    rng = np.random.default_rng(seed)
    n_tiles_src = 16
    src = jnp.asarray(rng.normal(size=(rows, n_tiles_src * cols)), jnp.float32)
    offs = jnp.asarray(rng.integers(0, n_tiles_src, size=t), jnp.int32)
    got = ops.pack_cols(src, offs, tile_cols=cols, block_rows=rows)
    want = ref.pack_cols_ref(src, offs, tile_cols=cols)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_pack_cols_dtypes(dtype):
    src = jnp.arange(8 * 64).reshape(8, 64).astype(dtype)
    offs = jnp.asarray([7, 0, 3], jnp.int32)
    got = ops.pack_cols(src, offs, tile_cols=8, block_rows=8)
    want = ref.pack_cols_ref(src, offs, tile_cols=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cols, block_cols", [
    (512, 128),    # the second grid axis divides the width
    (400, 128),    # ragged last column block
])
def test_pack_blocks_second_grid_axis(cols, block_cols):
    src = jnp.asarray(RNG.normal(size=(64, cols)), jnp.float32)
    offs = jnp.asarray([7, 0, 3, 7], jnp.int32)
    got = ops.pack_blocks(src, offs, tile_rows=8, block_cols=block_cols)
    want = ref.pack_blocks_ref(src, offs, tile_rows=8)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("rows, block_rows", [
    (64, 16),      # the second grid axis divides the height
    (60, 16),      # ragged last row block
])
def test_pack_cols_second_grid_axis(rows, block_rows):
    src = jnp.asarray(RNG.normal(size=(rows, 8 * 128)), jnp.float32)
    offs = jnp.asarray([5, 0, 2], jnp.int32)
    got = ops.pack_cols(src, offs, tile_cols=128, block_rows=block_rows)
    want = ref.pack_cols_ref(src, offs, tile_cols=128)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("shape, axis, dtype, want", [
    # 512^3 f32 along axis 0: 8-row tiles, 64 Ki-column blocks (2 MiB)
    ((512, 512, 512), 0, np.float32, (8, 65536)),
    # along axis 1: one 512-column tile per index, full 512-row height
    ((512, 512, 512), 1, np.float32, (512, 512)),
    # 2-D along axis 1: 128-lane tiles, the height cut to 2 MiB blocks
    ((16384, 16384), 1, np.float32, (128, 4096)),
    # packed dtypes take more rows per sublane group
    ((4096, 4096), 0, jnp.bfloat16, (16, 4096)),
    # small frames: a tile covering the whole axis is legal
    ((6, 40, 3), 1, np.float32, (120, 6)),
    ((5, 7), 0, np.float32, (5, 7)),
])
def test_pack_tiling_is_legal_and_bounded(shape, axis, dtype, want):
    from repro.core.redistribute import CompiledPlan, even_blocks, pack_tiling
    from repro.kernels.pack import BLOCK_BYTES, LANES, sublanes

    plan = CompiledPlan([((0,) * len(shape), shape)],
                        even_blocks(shape, 4, axis=axis), shape, dtype)
    geom = plan.pack_geometry
    tile, block = pack_tiling(geom, shape[axis], dtype)
    assert (tile, block) == want
    itemsize = np.dtype(dtype).itemsize
    assert tile * block * itemsize <= BLOCK_BYTES
    if geom.mode == "rows":
        extent, other = shape[0], geom.cols
        assert tile % sublanes(dtype) == 0 or tile == extent
        assert block % LANES == 0 or block == other
    else:
        extent, other = shape[axis] * geom.scale, geom.rows
        assert tile % LANES == 0 or tile == extent
        assert block % sublanes(dtype) == 0 or block == other


def test_pack_tiling_cuts_lane_tiles_to_the_block_budget(monkeypatch):
    from repro.core.redistribute import CompiledPlan, even_blocks, pack_tiling
    from repro.kernels import pack

    # (16, 17, 4, 5) along axis 1: runs of 20 columns, lcm(20, 128) = 640
    shape = (16, 17, 4, 5)
    plan = CompiledPlan([((0,) * 4, shape)], even_blocks(shape, 3, axis=1),
                        shape, np.float32)
    geom = plan.pack_geometry
    assert pack_tiling(geom, 17, np.float32) == (340, 16)  # the whole frame
    # one sublane group of 640 lanes no longer fits: 128-lane tiles, off
    # the runs, and the 16 rows move in two blocks of 8
    monkeypatch.setattr(pack, "BLOCK_BYTES", 8 * 128 * 4)
    assert pack_tiling(geom, 17, np.float32) == (128, 8)


def test_interpret_only_on_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret()
