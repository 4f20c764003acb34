"""The mixers' short convolution with its activation as one kernel
(``ops/kda.py conv_silu_kernel``) on the CPU, under the Pallas interpreter:
against the plain form (``short_conv``, then ``jax.nn.silu``) in float32, at
its seams (the first positions, the blocks of positions, the rows of the
batch, the first columns of a wider array), the rule that chooses the form,
and both callers with the kernel in place. Mosaic's lowering is
``ops/parity_checks.py check_short_conv``'s, on the chip."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from storm_tpu.models import kimi_linear as K  # noqa: E402
from storm_tpu.models import nemotron_h as N  # noqa: E402
from storm_tpu.models.registry import build_model  # noqa: E402
from storm_tpu.ops import kda  # noqa: E402
from storm_tpu.ops.platform import dispatch_notes  # noqa: E402

# three blocks of 16 positions (by two lane tiles), 8 positions at a time
BLOCKS = dict(rows=16, step=8)


def _plain(p, x):
    return jax.nn.silu(kda.short_conv(p, x[..., :p["w"].shape[1]]))


def _kernel(p, x, **blocks):
    return kda.conv_silu_kernel(p["w"], p.get("b"), x, interpret=True,
                                **{**BLOCKS, **blocks})


def _case(bias, shape=(2, 48, 256), channels=256, seed=0):
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    return (kda.short_conv_init(kp, channels, 4, bias=bias),
            jax.random.normal(kx, shape))


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_kernel_is_the_plain_form_in_float32(bias):
    p, x = _case(bias)
    got = _kernel(p, x)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(got, _plain(p, x), atol=1e-6, rtol=0)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_a_later_position_moves_nothing_before_it(bias):
    p, x = _case(bias)
    y, y2 = _kernel(p, x), _kernel(p, x.at[:, 5].add(1.0))
    np.testing.assert_array_equal(np.asarray(y[:, :5]), np.asarray(y2[:, :5]))
    # the taps reach three positions on and no further
    assert float(jnp.abs(y2[:, 5:9] - y[:, 5:9]).min()) > 0
    np.testing.assert_array_equal(np.asarray(y[:, 9:]), np.asarray(y2[:, 9:]))


def test_positions_before_the_first_read_as_zero():
    """Position ``t < width - 1`` sums its own taps alone: what the plain
    form gives for the sequence cut there, whatever a block's scratch held
    before (a second call's first block follows the first call's last)."""
    p, x = _case(True)
    y = _kernel(p, x)
    for t in range(3):
        want = p["b"] + sum(p["w"][3 - j] * x[:, t - j] for j in range(t + 1))
        np.testing.assert_allclose(y[:, t], jax.nn.silu(want), atol=1e-6)


@pytest.mark.parametrize("rows", [8, 16, 48])
def test_blocks_of_positions_hand_their_last_rows_on(rows):
    """48 positions as six blocks, three or one: the same values to the bit,
    and the plain form's at every seam."""
    p, x = _case(True)
    got = _kernel(p, x, rows=rows, step=8)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_kernel(p, x, rows=48)))
    seams = np.arange(48).reshape(-1, 8)[:, :3].ravel()
    np.testing.assert_allclose(got[:, seams], _plain(p, x)[:, seams],
                               atol=1e-6, rtol=0)


def test_nothing_crosses_from_one_row_of_the_batch_to_the_next():
    p, x = _case(False)
    y = _kernel(p, x)
    alone = _kernel(p, x[1:])
    np.testing.assert_array_equal(np.asarray(y[1:]), np.asarray(alone))
    moved = _kernel(p, x.at[0, -3:].add(5.0))
    np.testing.assert_array_equal(np.asarray(y[1]), np.asarray(moved[1]))


@pytest.mark.parametrize("wide", [256, 320, 572])
def test_the_first_columns_are_read_where_they_lie_in_a_wider_array(wide):
    """Nemotron's convolution takes the first 6,144 columns of a projection
    6,208 wide (``[x B C | dt]``: 48.5 lane tiles, the last one partial; here
    256 of 320 and of 572 = 4.47 tiles): the kernel is handed the whole
    array and no sliced copy is made for it."""
    p, x = _case(True, shape=(2, 48, wide))
    got = _kernel(p, x)
    assert got.shape == (2, 48, 256)
    np.testing.assert_allclose(got, _plain(p, x), atol=1e-6, rtol=0)


@pytest.mark.parametrize("seq,rows", [(512, 512), (1024, 1024), (1536, 512),
                                      (4096, 2048), (16384, 2048)])
def test_a_block_is_the_most_positions_that_divide_the_sequence(seq, rows):
    """Left to itself the kernel takes blocks of up to 2,048 positions: the
    grid it would run (read off the traced call, nothing is computed)."""
    p, _ = _case(False, channels=128)
    jaxpr = jax.make_jaxpr(lambda x: kda.conv_silu_kernel(
        p["w"], None, x, interpret=True))(
            jax.ShapeDtypeStruct((1, seq, 128), jnp.float32))
    assert f"grid=(1, 1, {seq // rows})" in str(jaxpr).replace("\n", " ")


def test_another_activation_is_an_argument_not_a_kernel():
    p, x = _case(True)
    got = kda.conv_silu_kernel(p["w"], p["b"], x, activation=jnp.tanh,
                               interpret=True, **BLOCKS)
    np.testing.assert_allclose(got, jnp.tanh(kda.short_conv(p, x)),
                               atol=1e-6, rtol=0)
    same = kda.conv_silu(p, x, activation=jnp.tanh)  # XLA's form, here
    np.testing.assert_array_equal(np.asarray(same),
                                  np.asarray(jnp.tanh(kda.short_conv(p, x))))


def test_bfloat16_in_and_out_is_rounded_once():
    """The serving type: the input widened in the kernel, taps, bias and SiLU
    in float32, one rounding. The float32 plain form on the same
    (bfloat16-rounded) input, rounded, is the same to the bit."""
    p, x = _case(True)
    xb = x.astype(jnp.bfloat16)
    got = _kernel(p, xb)
    assert got.dtype == jnp.bfloat16
    want = _plain(p, xb.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize(
    "channels,seq,width,platform,devices,no_pallas,want", [
        (4096, 4096, 4, "tpu", 1, False, "kernel"),  # Kimi-Linear's
        (6144, 4096, 4, "tpu", 1, False, "kernel"),  # Nemotron's
        (384, 512, 9, "tpu", 1, False, "kernel"),
        (4096, 4096, 4, "cpu", 1, False, "xla"),
        # a host with several chips (this suite itself: 8 host devices)
        (4096, 4096, 4, "tpu", 4, False, "xla"),
        (4096, 4096, 4, "tpu", 1, True, "xla"),   # STORM_TPU_NO_PALLAS
        (96, 4096, 4, "tpu", 1, False, "xla"),    # kimi_linear_tiny's
        (4160, 4096, 4, "tpu", 1, False, "xla"),  # half a lane tile over
        (4096, 4000, 4, "tpu", 1, False, "xla"),  # no whole blocks
        (4096, 256, 4, "tpu", 1, False, "xla"),   # less than a block
        (4096, 4096, 10, "tpu", 1, False, "xla"),  # taps past the carry
    ])
def test_conv_form_is_a_function_of_the_traced_shapes_and_the_devices(
        channels, seq, width, platform, devices, no_pallas, want,
        monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: [_Device(platform)])
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    if no_pallas:
        monkeypatch.setenv("STORM_TPU_NO_PALLAS", "1")
    else:
        monkeypatch.delenv("STORM_TPU_NO_PALLAS", raising=False)
    assert kda.conv_form(channels, seq, width) == want


@pytest.fixture
def conv_by_the_kernel(monkeypatch):
    """What a process with one TPU would build: the shape rule's own answer
    with the platform's two questions answered as there, and the kernel run
    by the Pallas interpreter."""
    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(kda, "conv_silu_kernel", functools.partial(
        kda.conv_silu_kernel, interpret=True))


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_choice_reaches_the_dispatch_notes(form, request):
    if form == "kernel":
        request.getfixturevalue("conv_by_the_kernel")
    p, x = _case(True, shape=(1, 512, 320))
    with dispatch_notes() as seen:
        got = kda.conv_silu(p, x)
    assert seen == [f"short_conv={form}"]
    np.testing.assert_allclose(got, _plain(p, x), atol=1e-6, rtol=0)


def test_xla_form_is_the_callers_old_expression_to_the_bit():
    p, x = _case(True, shape=(2, 9, 20), channels=6)
    np.testing.assert_array_equal(
        np.asarray(kda.conv_silu(p, x)),
        np.asarray(jax.nn.silu(kda.short_conv(p, x[..., :6]))))


def test_kda_mixer_with_the_kernel_is_the_mixer_without(conv_by_the_kernel,
                                                        monkeypatch):
    """Kimi-Linear's mixer at a head of a whole lane tile and a sequence of a
    whole block, float32: the three branches through the kernel (column 0 of
    their projections' results) against XLA's form."""
    monkeypatch.setattr(kda, "tables_form", lambda *a: "xla")  # not its test
    p = K.kda_mixer_init(jax.random.PRNGKey(0), 32, 1, 128, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 32))
    with dispatch_notes() as seen:
        got = K.kda_mixer(p, x, 1, 128, 64, 1e-5)
    assert "short_conv=kernel" in seen
    monkeypatch.setattr(kda, "conv_form", lambda *a: "xla")
    want = K.kda_mixer(p, x, 1, 128, 64, 1e-5)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_mamba_mixer_with_the_kernel_is_the_mixer_without(conv_by_the_kernel,
                                                          monkeypatch):
    """Nemotron's mixer, float32: ``x B C``, the first 384 columns of ``[x B
    C | dt]`` (386 wide: the last lane tile partial), with the bias."""
    args = (2, 64, 2, 64, 128, 1e-5)  # heads, head_dim, groups, state, chunk
    p = N.mamba_mixer_init(jax.random.PRNGKey(0), 32, *args[:4], 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 32))
    with dispatch_notes() as seen:
        got = N.mamba_mixer(p, x, *args)
    assert "short_conv=kernel" in seen
    monkeypatch.setattr(kda, "conv_form", lambda *a: "xla")
    want = N.mamba_mixer(p, x, *args)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


@pytest.mark.parametrize("name", ["kimi_linear_tiny", "nemotron_h_tiny"])
def test_a_cpu_program_notes_xlas_form(name):
    model = build_model(name)
    params, state = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1,) + tuple(model.input_shape), jnp.float32)
    with dispatch_notes() as seen:
        jax.eval_shape(lambda p, s, x: model.apply(p, s, x), params, state, x)
    assert "short_conv=xla" in seen and "short_conv=kernel" not in seen
