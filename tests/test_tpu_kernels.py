"""Compiled-on-TPU Pallas kernel parity (VERDICT r4 missing #1).

These are the non-interpret twins of tests/test_ops.py's kernel checks:
the same parity functions (storm_tpu/ops/parity_checks.py) with
``interpret=False``, which requires Mosaic — i.e. a real TPU. Under the
suite's CPU default they SKIP (not pass); run them on the chip with
``JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_kernels.py --no-header
-q -p no:cacheprovider``, the one entry to the compiled checks.
"""

import jax
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiled (non-interpret) Pallas kernels need a real TPU; "
           "interpret-mode math coverage lives in tests/test_ops.py",
)


@pytest.mark.slow
def test_flash_attention_compiled_parity():
    from storm_tpu.ops.parity_checks import check_flash_attention

    rows = check_flash_attention(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled flash_attention parity failures: {bad}"


@pytest.mark.slow
def test_short_attention_compiled_parity():
    from storm_tpu.ops.parity_checks import check_short_attention

    rows = check_short_attention(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled short_attention parity failures: {bad}"


@pytest.mark.slow
def test_w8a16_compiled_parity():
    from storm_tpu.ops.parity_checks import check_w8a16

    rows = check_w8a16(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled w8a16_matmul parity failures: {bad}"


@pytest.mark.slow
def test_kda_tables_compiled_parity():
    from storm_tpu.ops.parity_checks import check_kda_tables

    rows = check_kda_tables(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled kda_tables parity failures: {bad}"


@pytest.mark.slow
def test_kda_mixer_compiled_parity():
    from storm_tpu.ops.parity_checks import check_kda_mixer

    rows = check_kda_mixer(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled kda_mixer parity failures: {bad}"


@pytest.mark.slow
def test_short_conv_compiled_parity():
    from storm_tpu.ops.parity_checks import check_short_conv

    rows = check_short_conv(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled short_conv parity failures: {bad}"


@pytest.mark.slow
def test_causal_attention_compiled_parity():
    from storm_tpu.ops.parity_checks import check_causal_attention

    rows = check_causal_attention(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled causal_attention parity failures: {bad}"


@pytest.mark.slow
def test_ssd_scan_compiled_parity():
    from storm_tpu.ops.parity_checks import check_ssd_scan

    rows = check_ssd_scan(interpret=False)
    bad = [r for r in rows if not r["pass"]]
    assert not bad, f"compiled ssd_scan parity failures: {bad}"
