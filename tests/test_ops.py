"""Op-level numerics: layers + attention reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.ops import layers as L
from storm_tpu.ops.attention import attention_reference, mha_init, multi_head_attention


def test_dense_matches_numpy():
    rng = jax.random.PRNGKey(0)
    p = L.dense_init(rng, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    got = L.dense(p, x)
    want = np.asarray(x) @ np.asarray(p["w"]) + np.asarray(p["b"])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_conv_identity_kernel():
    # 1x1 identity conv leaves channels unchanged.
    p = {"w": jnp.eye(3).reshape(1, 1, 3, 3)}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 5, 3))
    np.testing.assert_allclose(np.asarray(conv := L.conv2d(p, x)), np.asarray(x), atol=1e-6)


def test_pooling():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    mp = L.max_pool(x)
    ap = L.avg_pool(x)
    assert mp.shape == (1, 2, 2, 1)
    assert float(mp[0, 0, 0, 0]) == 5.0
    assert float(ap[0, 0, 0, 0]) == 2.5


def test_batchnorm_train_normalizes():
    p, s = L.batchnorm_init(4)
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 4)) * 5 + 3
    y, new_s = L.batchnorm(p, s, x, train=True)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, 0)), np.zeros(4), atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.std(y, 0)), np.ones(4), atol=1e-2)
    assert not np.allclose(np.asarray(new_s["mean"]), 0)


def test_layernorm():
    p = L.layernorm_init(8)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8)) * 4 + 2
    y = L.layernorm(p, x)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), np.zeros((2,)), atol=1e-5)


def test_attention_reference_softmax_rows():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 4, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 4, 8))
    out = attention_reference(q, k, v)
    assert out.shape == (1, 2, 4, 8)
    # attention output is a convex combination of v rows: bounded by v range
    assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-5
    assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-5


def test_mha_shapes():
    p = mha_init(jax.random.PRNGKey(0), 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 32))
    y = multi_head_attention(p, x, 4)
    assert y.shape == (2, 10, 32)


@pytest.mark.slow
def test_flash_attention_matches_reference_interpret():
    """Pallas kernel (interpreter on CPU) vs the jnp reference path —
    includes the ViT-B/16 shape (197 padded) and a multi-KV-chunk case."""
    from storm_tpu.ops.flash_attention import flash_attention

    for b, h, s, d in [(1, 2, 197, 64), (2, 1, 64, 32), (1, 1, 600, 64)]:
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d), jnp.float32)
            for i in range(3)
        )
        want = attention_reference(q, k, v)
        got = flash_attention(q, k, v, interpret=True, block_k=256)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


def test_flash_attention_without_causal_gives_what_it_gave():
    """The non-causal form through the shared kernel: 64-wide heads padded to
    a lane tile, 150 keys padded into two blocks of 128 (the first needs no
    mask, the second hides its tail), two query tiles."""
    from storm_tpu.ops.flash_attention import flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 2, 150, 64))
               for i in range(3))
    got = flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, attention_reference(q, k, v), atol=2e-6)


def _plain_causal(q, k, v, scale):
    """The whole masked float32 softmax, each key head written out for the
    query heads that read it."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    seen = jnp.tril(jnp.ones((q.shape[2],) * 2, bool))
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), -1), v)


@pytest.mark.parametrize("hq,hkv,s,dk,dv,block_q,block_k,dtype,atol", [
    # latent attention's widths: keys a lane tile and a half, values one
    (4, 4, 384, 192, 128, 128, 128, jnp.float32, 5e-6),
    # grouped: 4 query heads over 2 key heads, stacked 2 x 64 rows a tile;
    # three key blocks, so tiles 2-5 skip blocks and tile 0 meets one only
    (4, 2, 384, 128, 128, 64, 128, jnp.float32, 5e-6),
    (4, 4, 384, 128, 128, 64, 128, jnp.float32, 5e-6),
    # a query tile wider than a key block: two masked blocks on the diagonal
    (2, 2, 512, 128, 128, 256, 128, jnp.float32, 5e-6),
    # key blocks wider than a tile: the diagonal block is part of a wide one
    (4, 1, 256, 128, 128, 16, 128, jnp.float32, 5e-6),
    # 300 tokens padded into 512 and 24/16-wide heads padded to a lane tile
    (4, 2, 300, 24, 16, 64, 128, jnp.float32, 5e-6),
    # the queries padded further (512) than the keys (384): the last tile's
    # loop ends with the keys
    (2, 1, 300, 128, 128, 256, 128, jnp.float32, 5e-6),
    # the serving type: weights go to the value product in bf16
    (4, 2, 256, 192, 128, 32, 128, jnp.bfloat16, 2e-2),
])
def test_causal_kernel_against_the_plain_softmax_and_the_blocked_form(
        hq, hkv, s, dk, dv, block_q, block_k, dtype, atol):
    """The causal form of ops/flash_attention.py under the interpreter: the
    mathematics (Mosaic's lowering is ops/parity_checks.py's, on the chip)."""
    from storm_tpu.ops.attention import causal_blocked
    from storm_tpu.ops.flash_attention import flash_attention

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, h, s, d)
                                 ).astype(dtype)
               for i, (h, d) in enumerate(((hq, dk), (hkv, dk), (hkv, dv))))
    scale = dk ** -0.5
    got = flash_attention(q, k, v, scale=scale, block_q=block_q,
                          block_k=block_k, causal=True, interpret=True)
    assert got.shape == (2, hq, s, dv) and got.dtype == dtype
    f32 = [y.astype(jnp.float32) for y in (q, k, v)]
    np.testing.assert_allclose(got.astype(jnp.float32),
                               _plain_causal(*f32, scale), atol=atol)
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        causal_blocked(q, k, v, scale, block=64).astype(jnp.float32),
        atol=atol)
    # one row of the batch, read where it lies
    one = flash_attention(q, k, v, scale=scale, block_q=block_q,
                          block_k=block_k, causal=True, interpret=True, row=1)
    np.testing.assert_array_equal(np.asarray(one, np.float32),
                                  np.asarray(got[1:], np.float32))


def test_a_key_in_the_padded_tail_never_receives_weight():
    """Every real score is far below zero, a padded key's is zero: had one
    leaked into a softmax it would take all the weight and the values' mean
    would fall to the padding's zero."""
    from storm_tpu.ops.flash_attention import flash_attention

    q = jnp.full((1, 2, 200, 128), 2.0)
    k, v = -q, jnp.ones((1, 2, 200, 128))
    for causal in (True, False):
        got = flash_attention(q, k, v, block_q=128, block_k=128,
                              causal=causal, interpret=True)
        np.testing.assert_allclose(got, v, atol=1e-6)


@pytest.mark.parametrize("hq,hkv,s,dk,dv,on_tpu,devices,want", [
    (32, 32, 4096, 192, 128, True, 1, "kernel"),   # Kimi-Linear's cell
    (32, 2, 4096, 128, 128, True, 1, "kernel"),    # Nemotron's cell
    (32, 32, 4096, 192, 128, False, 1, "blocked"),  # off the TPU
    # a host with several chips: the kernel has no partitioning rule
    (32, 2, 4096, 128, 128, True, 4, "blocked"),
    (32, 2, 4000, 128, 128, True, 1, "blocked"),   # no whole number of tiles
    (4, 2, 1024, 24, 16, True, 1, "blocked"),      # widths it would pad
    (4, 2, 1024, 128, 64, True, 1, "blocked"),
])
def test_causal_form_is_a_function_of_the_traced_shapes_and_the_devices(
        hq, hkv, s, dk, dv, on_tpu, devices, want, monkeypatch):
    from storm_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: on_tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert attention.causal_form(hq, hkv, s, dk, dv) == want


@pytest.mark.parametrize("hq,hkv,want", [(4, 4, "kernel"),
                                         (4, 2, "kernel-grouped")])
def test_causal_attention_through_the_kernel_is_the_blocked_forms(
        hq, hkv, want, monkeypatch):
    """``causal_attention`` built with the kernel (the rule answered here, in
    the test, as a chip would for whole tiles) against the blocked form it is
    built with on the CPU, and what it notes either way."""
    import functools

    from storm_tpu.ops import attention, flash_attention as fa
    from storm_tpu.ops.platform import dispatch_notes

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (3, h, 256, 128))
               for i, h in enumerate((hq, hkv, hkv)))
    with dispatch_notes() as seen:
        blocked = attention.causal_attention(q, k, v, block=64)
    assert seen == ["causal_attention=" + want.replace("kernel", "blocked")]
    monkeypatch.setattr(attention, "causal_form", lambda *a: "kernel")
    monkeypatch.setattr(fa, "causal_tiles", lambda group: (64 // group, 128))
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    with dispatch_notes() as seen:
        got = jax.jit(attention.causal_attention)(q, k, v)
    assert seen == ["causal_attention=" + want]
    np.testing.assert_allclose(got, blocked, atol=2e-6)


def test_w8a16_matmul_matches_dequant_reference():
    """Pallas fused dequant-matmul (interpreter on CPU) vs explicit
    dequantize-then-dot, over shapes that exercise M/N/K padding and
    3-D activations (the ViT token layout)."""
    from storm_tpu.infer.engine import quantize_params
    from storm_tpu.ops.quant_matmul import w8a16_matmul

    rng = np.random.RandomState(0)
    for xshape, k, n in [
        ((4, 64), 64, 128),        # exact tiles
        ((5, 100), 100, 70),       # every axis padded
        ((2, 9, 48), 48, 200),     # 3-D activations, N > block_n
        ((1, 700), 700, 10),       # K > block_k (multi-chunk loop)
    ]:
        x = jnp.asarray(rng.randn(*xshape), jnp.float32)
        w = jnp.asarray(rng.randn(k, n), jnp.float32)
        q = quantize_params({"w": w})["w"]
        want = x @ (q["__q"].astype(jnp.float32) * q["__s"])
        got = w8a16_matmul(x, q["__q"], q["__s"], interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_dense_dispatches_on_quantized_weights():
    """layers.dense must route {"__q","__s"} weights through the fused
    path (jnp fallback off-TPU) and match the float layer closely."""
    from storm_tpu.infer.engine import quantize_params
    from storm_tpu.ops import layers as L

    rng = jax.random.PRNGKey(3)
    p = L.dense_init(rng, 32, 16)
    x = jax.random.normal(jax.random.PRNGKey(4), (6, 32), jnp.float32)
    want = L.dense(p, x)
    qp = {"w": quantize_params({"w": p["w"]})["w"], "b": p["b"]}
    got = L.dense(qp, x)
    assert np.max(np.abs(np.asarray(got - want))) < 0.05


# ---- attention for many rows of a short sequence (ops/short_attention.py) ---

_SHORT_CASES = [
    # the benchmark cells' tokens and width: 257 is no sublane multiple,
    # the 88-wide heads are lane slices at offsets that are no lane multiple
    pytest.param((2, 257, 1408), 16, jnp.bfloat16, 2e-2, id="g14-257x1408-bf16"),
    pytest.param((1, 197, 768), 12, jnp.bfloat16, 2e-2, id="b16-197x768-bf16"),
    pytest.param((3, 33, 96), 4, jnp.float32, 1e-5, id="toy-33x96-f32"),
    pytest.param((2, 8, 40), 5, jnp.float32, 1e-5, id="toy-8x40-f32"),
    # the kernel's rules (ops/short_attention.py): a head is read in the lane
    # tile that holds it, one that straddles two folded into one (the cases
    # above: ten heads of sixteen folded, two heads a tile, a row under a
    # tile), and up to eight keys past the last whole lane tile are columns
    # (above: 256 + 1; 197, 33 and 8 whole)
    pytest.param((2, 257, 1408), 16, jnp.float32, 1e-5, id="g14-257x1408-f32"),
    pytest.param((1, 197, 768), 12, jnp.float32, 1e-5, id="b16-197x768-f32"),
    *(pytest.param(shape, heads, dtype, atol,
                   id=f"{name}-{shape[1]}x{shape[2]}-{tag}")
      for name, shape, heads in [
          ("heads128", (2, 256, 1024), 8),  # no mask, no odd key
          ("tile+1", (2, 129, 352), 4),     # one tile of keys and a column
          ("odd8", (2, 264, 1408), 16),     # eight columns, the rule's edge
          ("odd9", (2, 265, 1408), 16),     # nine: the score tile whole
      ]
      for tag, dtype, atol in [("f32", jnp.float32, 1e-5),
                               ("bf16", jnp.bfloat16, 2e-2)]),
]


@pytest.mark.parametrize("s,form", [
    (257, "256+1"), (264, "256+8"), (129, "128+1"), (265, "whole"),
    (256, "whole"), (197, "whole"), (33, "whole"), (8, "whole")])
def test_short_attention_key_split_by_shape(s, form):
    """Keys past the last whole lane tile are columns when they are eight or
    fewer and a whole tile lies before them; the program's note says so."""
    from storm_tpu.ops.short_attention import key_split, keys_form

    assert keys_form(s) == form
    n, r = key_split(s)
    assert n + r == s and (r == 0 or (n % 128 == 0 and 0 < r <= 8))


def _qkv(shape, dtype):
    return tuple(
        jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32).astype(dtype)
        for i in range(3))


@pytest.mark.parametrize("shape,heads,dtype,atol", _SHORT_CASES)
def test_short_attention_kernel_matches_reference(shape, heads, dtype, atol):
    """The Pallas row kernel (interpreter) vs the jnp attention on the same
    [B, S, H*D] operands, float32 reference."""
    from storm_tpu.ops.short_attention import _forward, reference

    q, k, v = _qkv(shape, dtype)
    got = _forward(q, k, v, heads=heads, interpret=True)
    assert got.shape == shape and got.dtype == dtype
    want = reference(*(a.astype(jnp.float32) for a in (q, k, v)), heads)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=atol)


@pytest.mark.parametrize("shape,heads", [((2, 33, 96), 4), ((1, 257, 176), 2)])
def test_short_attention_gradient_is_the_references(shape, heads, monkeypatch):
    """custom_vjp: a backward needs the scores again and ``pallas_call`` has
    none, so under ``jax.grad`` both passes are jax's own of the jnp path: the
    differentiated program holds no kernel (training pays for no forward
    twice) and its values and gradients are the reference's."""
    import functools

    from storm_tpu.ops import short_attention as sa

    monkeypatch.setattr(sa, "_forward",
                        functools.partial(sa._forward, interpret=True))
    q, k, v = _qkv(shape, jnp.float32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, heads) ** 2)

    through_kernel = jax.value_and_grad(
        functools.partial(loss, sa.short_attention), argnums=(0, 1, 2))
    assert "pallas_call" in str(jax.make_jaxpr(
        functools.partial(loss, sa.short_attention))(q, k, v))
    assert "pallas_call" not in str(jax.make_jaxpr(through_kernel)(q, k, v))
    lk, gk = through_kernel(q, k, v)
    lr, gr = jax.value_and_grad(functools.partial(loss, sa.reference),
                                argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lk), float(lr), rtol=1e-5)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("shape,heads,itemsize,on_tpu,devices,want", [
    ((8, 257, 1408), 16, 2, True, 1, "rows"),    # the paced cell's program
    ((32, 257, 1408), 16, 2, True, 1, "rows"),
    ((128, 257, 1408), 16, 2, True, 1, "rows"),
    ((256, 257, 1408), 16, 2, True, 1, "rows"),  # the backlog cell's program
    ((7, 257, 1408), 16, 2, True, 1, "xla"),     # 7.4 million scores: under
    ((16, 197, 768), 12, 2, True, 1, "xla"),     # ViT-B/16: XLA ahead, measured
    ((32, 197, 768), 12, 2, True, 1, "rows"),    # ViT-B/16: kernel ahead, measured
    ((8, 2048, 256), 2, 2, True, 1, "flash"),    # long sequences stay flash's
    ((64, 900, 2048), 16, 4, True, 1, "xla"),    # many scores, no room in VMEM
    ((256, 257, 1408), 16, 2, False, 1, "xla"),  # off TPU: always the jnp path
    ((64, 65, 64), 4, 4, True, 1, "xla"),        # toy widths (vit_tiny)
    # a host with several chips: a program may be split over them, and the
    # kernel has no partitioning rule (engines, train steps, dry runs alike)
    ((256, 257, 1408), 16, 2, True, 4, "xla"),
    ((8, 2048, 256), 2, 2, True, 4, "flash"),    # as before this rule
])
def test_attention_form_is_a_function_of_the_traced_shapes_and_the_devices(
        shape, heads, itemsize, on_tpu, devices, want, monkeypatch):
    from storm_tpu.ops import attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: on_tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert attention.attention_form(*shape, heads, itemsize) == want


def test_dispatch_notes_observe_and_decide_nothing(monkeypatch):
    """The notes are what a trace saw, nested traces restore what they found,
    and no rule reads them: the same shapes give the same form inside and
    outside a ``dispatch_notes`` block."""
    from storm_tpu.ops import attention
    from storm_tpu.ops.platform import dispatch_notes, note, one_device

    assert not one_device()  # conftest gives the CPU backend eight devices
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert one_device()
    outside = attention.attention_form(256, 257, 1408, 16, 2)
    with dispatch_notes() as outer:
        note("attention", "rows")
        with dispatch_notes() as inner:
            assert attention.attention_form(256, 257, 1408, 16, 2) == outside
            note("attention", "xla")
            note("attention", "xla")
        note("norm", "xla")
    note("attention", "flash")  # no block open: dropped
    assert inner == ["attention=xla"]
    assert outer == ["attention=rows", "norm=xla"]


def test_mha_through_the_row_kernel_matches_the_xla_path(monkeypatch):
    """multi_head_attention built with the row kernel (forced here, in the
    test, as a chip would choose it at many rows) against the jnp path, and
    the choice lands in the dispatch notes the engine reads."""
    import functools

    from storm_tpu.ops import attention, short_attention as sa
    from storm_tpu.ops.platform import dispatch_notes

    p = mha_init(jax.random.PRNGKey(0), 96, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 33, 96))
    with dispatch_notes() as seen:
        want = multi_head_attention(p, x, 4)
    assert seen == ["attention=xla"]
    monkeypatch.setattr(sa, "_forward",
                        functools.partial(sa._forward, interpret=True))
    monkeypatch.setattr(attention, "attention_form", lambda *a: "rows")
    with dispatch_notes() as seen:
        got = multi_head_attention(p, x, 4)
    assert seen == ["attention=rows", "rows_keys=whole"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---- Pallas-vs-reference dispatch predicate (ops/platform.py) ---------------


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def _raising_devices():
    raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: Internal "
                       "error when accessing libtpu multi-process lockfile.")


@pytest.mark.parametrize("devices,no_pallas,want", [
    (lambda: [_FakeDevice("cpu")], "", False),
    (lambda: [_FakeDevice("tpu")], "", True),
    (lambda: [_FakeDevice("tpu")], "1", False),
    # the escape hatch is honoured before the backend is asked
    (_raising_devices, "1", False),
    # a chip that cannot be opened is an error, never the jnp path
    (_raising_devices, "", RuntimeError),
], ids=["cpu", "tpu", "tpu_forced_off", "forced_off_no_backend",
        "backend_error_propagates"])
def test_use_pallas_dispatch(monkeypatch, devices, no_pallas, want):
    from storm_tpu.ops import platform

    monkeypatch.setattr(platform.jax, "devices", devices)
    monkeypatch.setenv("STORM_TPU_NO_PALLAS", no_pallas)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="lockfile"):
            platform.use_pallas()
    else:
        assert platform.use_pallas() is want


def test_open_devices_names_a_held_chip(monkeypatch):
    """libtpu's lockfile error tells the operator to delete the lock; the
    serving path says what really happened: another process holds the chip."""
    from storm_tpu.parallel import mesh

    monkeypatch.setattr(mesh.jax, "devices", _raising_devices)
    with pytest.raises(RuntimeError,
                       match="a chip belongs to one process at a time"):
        mesh.make_mesh()

    def other_error():
        raise RuntimeError("Unknown backend 'tpu'")

    monkeypatch.setattr(mesh.jax, "devices", other_error)
    with pytest.raises(RuntimeError, match="Unknown backend"):
        mesh.open_devices()
