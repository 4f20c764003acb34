"""The models' parts under their names (PR 41): the lowered program of each
family carries every name its model should have in its operations' paths and
none from outside ``ops/parts.py``'s vocabulary, and a name changes no
value."""

import contextlib
import re

import jax
import numpy as np
import pytest

from storm_tpu.models.registry import build_model, load_or_init
from storm_tpu.ops import parts

COMMON = {parts.EMBED, parts.HEAD, parts.NORM, parts.PROJ,
          parts.MIX_ELEMENTWISE, parts.MIX_ATTENTION}
MOE = {parts.MOE_ROUTE, parts.MOE_EXPERTS, parts.MOE_COMBINE}
EXPECTED = {
    "vit_tiny": COMMON,
    "kimi_linear_tiny": COMMON | MOE | {parts.MIX_KDA_TABLES,
                                        parts.MIX_KDA_SCAN},
    "nemotron_h_tiny": COMMON | MOE | {parts.MIX_SSD_SCAN},
    "kimi_k2_tiny": COMMON | MOE | {parts.MIX_ROPE},
    # its window of 96 selects: the dense form's ``mix.attention`` is not run
    "minicpm_sala_tiny": (COMMON - {parts.MIX_ATTENTION}) | {
        parts.MIX_SPARSE_SELECT, parts.MIX_SPARSE_ATTENTION,
        parts.MIX_SSD_SCAN, parts.MIX_ROPE},
    "evabyte_tiny": (COMMON - {parts.MIX_ATTENTION}) | {
        parts.MIX_EVA_CHUNKS, parts.MIX_EVA_ATTENTION, parts.MIX_ROPE},
    # four layers whose queries read a window, one that reads every key
    "trinity_tiny": COMMON | MOE | {parts.MIX_WINDOW_ATTENTION,
                                    parts.MIX_ROPE},
    # 40 positions into 12 keys a query: the indexer's first pass and the
    # masked second pass; the dense form's ``mix.attention`` is not run
    "keye_tiny": (COMMON - {parts.MIX_ATTENTION}) | MOE | {
        parts.MIX_INDEX_SELECT, parts.MIX_SPARSE_ATTENTION, parts.MIX_ROPE},
    # Mamba-2 blocks and one attention block, an expert layer in each; the
    # tied product lies under ``head``: no part of its own
    "granite_h_tiny": COMMON | MOE | {parts.MIX_SSD_SCAN},
    # every block both mixers on one norm, then a feed-forward under a part
    # of its own: ``proj`` is the mixers' projections alone
    "falcon_h1_tiny": COMMON | {parts.MIX_SSD_SCAN, parts.MIX_ROPE,
                                parts.FFN},
    # gated short convolutions under a part of their own, one rotary
    # attention layer with head norms; two dense layers' ``ffn``, then experts
    "lfm2_tiny": COMMON | MOE | {parts.MIX_GATED_CONV, parts.MIX_ROPE,
                                 parts.FFN},
    # a sandwich of rotary attention and a feed-forward under its own part,
    # run four times as the body of one loop that carries no part's name
    "ouro_tiny": COMMON | {parts.MIX_ROPE, parts.FFN},
}
# the loop over a looped model's passes is the whole step and no part: what
# is its own (the counter's increment) lies under no name, every operation
# of a pass under its part's as in any other plan
PASSES = re.compile(r"(jit\(fwd\)/)?while/body/[^/]+")


def _forward(name):
    model = build_model(name)
    params, state = load_or_init(model, None, 3)
    rng = np.random.default_rng(0)
    shape = (2, *model.input_shape)
    x = (rng.integers(0, model.num_classes, shape) if len(shape) == 2
         else rng.normal(size=shape)).astype(np.float32)

    def fwd(p, s, xx):
        return model.apply(p, s, xx, train=False)[0]

    return fwd, (params, state, x)


def _paths(text):
    """The name-stack paths of a lowered program's operations: those from
    the program's root, and those of a loop's body that jax lowers as a
    function of its own, whose paths begin at the body (every operation of a
    looped model's pass lies in one)."""
    return set(re.findall(r'loc\("(jit\([^"]*|[\w.]+/[^"\[\]]*)"', text))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_lowered_program_names_the_models_parts_and_no_other(name):
    fwd, args = _forward(name)
    paths = _paths(jax.jit(fwd).lower(*args).as_text(debug_info=True))
    assert paths
    found = {parts.part_of(p) for p in paths} - {None}
    assert found == EXPECTED[name]
    # a piece that looks like a part's name is one: the vocabulary is flat
    # and closed, so a reader's table and a model's scopes cannot drift apart
    dotted = {piece for p in paths for piece in p.split("/")
              if re.fullmatch(r"(mix|moe)\.\w+", piece)}
    assert dotted <= set(parts.VOCABULARY)
    # inside a loop the body's operations keep the loop's name (ViT has none)
    loops = [p for p in paths if "/while/body" in p]
    assert all(parts.part_of(p) for p in loops if not PASSES.fullmatch(p))
    assert any(PASSES.fullmatch(p) for p in loops) == (name == "ouro_tiny")
    assert bool(loops) == (name != "vit_tiny")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_name_changes_no_value(name, monkeypatch):
    fwd, args = _forward(name)
    named = np.asarray(jax.jit(fwd)(*args))

    def bare(*a):  # the same trace with every scope a no-op
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda _name: contextlib.nullcontext())
            return fwd(*a)

    text = jax.jit(bare).lower(*args).as_text(debug_info=True)
    assert not {parts.part_of(p) for p in _paths(text)} - {None}
    assert np.array_equal(named, np.asarray(jax.jit(bare)(*args)))


def test_the_innermost_name_is_the_operations():
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/proj/dot_general") == parts.PROJ
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/mix.attention/while/body/exp") == \
        parts.MIX_ATTENTION
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/mix.sparse_select/while/body/top_k") == \
        parts.MIX_SPARSE_SELECT
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/mix.window_attention/while/body/exp") == \
        parts.MIX_WINDOW_ATTENTION
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/mix.index_select/jit(_select_kernel_row)/"
        "pallas_call") == parts.MIX_INDEX_SELECT
    # a feed-forward that names itself: its loop over rows is ``ffn``'s
    assert parts.part_of(
        "jit(fwd)/ffn/while/body/closed_call/dot_general") == parts.FFN
    # a convolution operator's own pass, inside the mixer's scope
    assert parts.part_of(
        "jit(fwd)/mix.elementwise/mix.gated_conv/pallas_call") == \
        parts.MIX_GATED_CONV
    assert parts.part_of("jit(fwd)/jit(main)/reduce_sum") is None
    assert len(set(parts.VOCABULARY)) == len(parts.VOCABULARY) == 21


def test_under_the_compile_caches_settings_the_names_reach_the_compiled_program():
    """``enable_compile_cache`` keys the cache on metadata and trims the
    locations to an operation's own frame. The compiled program must still
    carry the parts (with ``jax_include_full_tracebacks_in_locations`` off
    jax writes the names in a form from which XLA drops the scopes: seen on
    the chip, PR 41), and nothing of who called (two entry points share an
    engine's executables)."""
    from storm_tpu.infer.engine import key_on_metadata

    names = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit",
             "jax_hlo_source_file_canonicalization_regex")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        key_on_metadata()
        fwd, args = _forward("vit_tiny")
        lowered = jax.jit(fwd).lower(*args)
        compiled = lowered.compile().as_text()
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
    found = {parts.part_of(p) for p in
             re.findall(r'op_name="([^"]*)"', compiled)} - {None}
    assert found >= {parts.PROJ, parts.MIX_ATTENTION, parts.NORM, parts.HEAD}
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    assert '"storm_tpu/models/vit.py"' in asm  # relative to the checkout
    assert "callsite(" not in asm and "_pytest" not in asm
