"""Compile the main path's kernels for a *described* TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached (on-chip-measurement guide,
section 2, rehearsal 3). That catches what interpret mode cannot: a slice
not aligned to the tiling, more fast memory than a kernel may use, a
kernel the compiler refuses to place in a whole program. Nothing runs, so
these say nothing of results or times — ``chip_smoke.py`` does that on the
chip. Skipped where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host: four devices, none attached."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described: {e!r}")


@pytest.fixture(scope="module")
def v5e(topo):
    """One device of that host, as a sharding."""
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next compile warns and
    compiles again), so the cache is off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(8, 2, 2048, 128), (1, 8, 4096, 64)])
def test_flash_attention_compiles_for_v5e(v5e, shape):
    """longseq_encoder's serving shape (batch 8, 2 heads of 128) and a long
    S=4096 shape with 64-wide heads (padded to the 128-lane tile)."""
    from storm_tpu.ops.flash_attention import flash_attention

    q = _spec(shape, jnp.bfloat16, v5e)
    text = flash_attention.lower(q, q, q).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("hq,hkv,dk,dv,carried", [
    (32, 32, 192, 128, "bf16[8,32,4096,192]"),  # Kimi-Linear's cell
    (32, 2, 128, 128, "bf16[8,2,4096,128]"),    # Nemotron's cell
    (64, 8, 128, 128, "bf16[8,8,4096,128]"),    # Solar Open 2's, at 8 rows
])
def test_causal_attention_compiles_as_a_loop_of_kernel_calls(
        v5e, monkeypatch, hq, hkv, dk, dv, carried):
    """The language cells' attention at their bucket of 8 windows of 4,096,
    as one chip builds it: the kernel is in the program (192-wide keys read
    as they lie), the rows are one ``while`` that still carries q, k and v in
    the shapes the benchmark's ``mla_attention_ms`` / ``gqa_attention_ms``
    find it by, nothing is cut out of them inside it, and no block of
    float32 scores is left in HBM."""
    import re

    import storm_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "_one_device", lambda: True)
    assert attention.causal_form(hq, hkv, 4096, dk, dv) == "kernel"
    q, k, v = (_spec((8, 4096, h, d), jnp.bfloat16, v5e)
               for h, d in ((hq, dk), (hkv, dk), (hkv, dv)))
    text = jax.jit(lambda q, k, v: attention.causal_attention(
        *(y.transpose(0, 2, 1, 3) for y in (q, k, v)), scale=dk ** -0.5
    ).transpose(0, 2, 1, 3)).lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text
    (loop,) = [line for line in text.splitlines() if " while(" in line]
    assert loop.count(carried) >= 2
    assert not re.search(r"bf16\[[\d,]+\]\S* dynamic-slice\(", text)
    assert "f32[32,512," not in text and "f32[2,16,512," not in text


@pytest.mark.parametrize("window,part", [
    (2048, "mix.window_attention"), (None, "mix.attention")])
def test_trinitys_two_attention_loops_compile_with_the_same_shapes_and_their_own_names(
        v5e, monkeypatch, window, part):
    """Trinity's attention at its bucket of 4 windows of 16,384, 32 query
    heads on 4 key heads, as one chip builds it, with a window of 2,048 keys
    and without: the kernel is in the program either way, the rows are one
    ``while`` that carries q, k and v whole (nothing is cut out of them
    inside it), in the same shapes in both, and only the part's name in the
    loop's metadata tells the two apart, which is what the benchmark's
    ``window_attention_ms`` and ``trinity_full_attention_ms`` read."""
    import re

    import storm_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "_one_device", lambda: True)
    assert attention.causal_form(32, 4, 16384, 128, 128) == "kernel"
    q, k, v = (_spec((4, 16384, h, 128), jnp.bfloat16, v5e)
               for h in (32, 4, 4))
    text = jax.jit(lambda q, k, v: attention.causal_attention(
        *(y.transpose(0, 2, 1, 3) for y in (q, k, v)), scale=128 ** -0.5,
        window=window).transpose(0, 2, 1, 3)).lower(q, k, v).compile(
        ).as_text()
    assert "tpu_custom_call" in text
    (loop,) = [line for line in text.splitlines() if " while(" in line]
    assert loop.count("bf16[4,4,16384,128]") >= 2
    assert "bf16[4,32,16384,128]" in loop
    assert f"/{part}/while" in loop
    assert not re.search(r"bf16\[[\d,]+\]\S* dynamic-slice\(", text)
    assert "f32[32,512," not in text and "f32[4,8,512," not in text


@pytest.mark.parametrize("window,part,parents_operations", [
    (2048, "mix.window_attention", 274), (None, "mix.attention", 204)])
def test_trinitys_mixer_compiles_with_its_heads_merged_from_projection_to_projection(
        v5e, monkeypatch, window, part, parents_operations):
    """Trinity's mixer whole at its cell's step (4 windows of 16,384 into
    2,048, 32 query heads on 4 key heads of 128, bfloat16), a sliding layer
    and a full one, as one chip builds it: three kernels (the head norm of q
    and of k, with the turn in its pass in a sliding layer; the attention);
    the rows are one ``while`` under the part's own name, which carries q and
    its result ``(4, 16384, 4096)`` and k and v ``(4, 16384, 512)`` as the
    projections left them; no view a head exists anywhere, in float32 (the
    parent's head norms: three passes over 1.07 GB) or in bfloat16 (its two
    transpositions around the loop), no float32 array of q's size is written,
    and nothing of q's size is copied, re-tiled or converted outside the
    kernels. The parent's temporaries were 2.69 GB a sliding mixer, 1.14
    now; its compiled text held 274 and 204 operations (163 and 127 now: a
    warm load follows the text's size, PERF.md section 7 item 15), and a
    change that unrolls something into either shows here."""
    import re

    import storm_tpu.ops.attention as attention
    import storm_tpu.ops.rope as rope
    from storm_tpu.models.minicpm_sala import minicpm4_mixer_init
    from storm_tpu.models.trinity import trinity_mixer

    for module in (attention, rope):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert attention.merged_form(32, 4, 16384, 128, 128) == "kernel"
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: minicpm4_mixer_init(
            jax.random.PRNGKey(0), 2048, 32, 4, 128)))
    x = _spec((4, 16384, 2048), jnp.bfloat16, v5e)
    tables = _spec((16384, 64), jnp.float32, v5e)
    compiled = jax.jit(lambda p, x, cos, sin: trinity_mixer(
        p, x, 32, 4, 128, 1e-5, (cos, sin), window)).lower(
        p, x, tables, tables).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    (loop,) = _loops(text)
    assert f"/{part}/while" in loop
    assert loop.count("bf16[4,16384,4096]") >= 2
    assert loop.count("bf16[4,16384,512]") >= 2
    assert not re.search(
        r"\[4,16384,(32|4),128\]|\[8192,8,(32|4),128\]|\[4,(32|4),16384,128\]",
        text)
    entry = text[text.index("ENTRY"):]
    assert "f32[4,16384,4096]" not in entry + loop
    assert not re.search(r"= \w+\[4,16384,4096\]\S* "
                         r"(copy|reshape|transpose|convert|slice)\(", entry)
    assert len(re.findall(r"^\s+(?:ROOT )?%\S+ = ", text, re.M)) \
        <= parents_operations
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 2 ** 30


def _kernel_modules(lowered):
    """The Mosaic modules of a lowered program's Pallas calls, printed
    without debug info (a body is serialized with its source lines)."""
    import base64
    import re

    from jaxlib.mlir import ir

    texts = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered.as_text()):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            texts.append(ir.Module.parse(base64.b64decode(body)).operation
                         .get_asm(enable_debug_info=False))
    return texts


def test_trinitys_window_kernel_walks_back_from_the_tiles_end_and_its_full_layers_is_the_parents(
        v5e):
    """One row's call of the merged entry at the published sizes (32 query
    heads on 4 key heads of 128, 16,384 positions, tiles of 64 x 512), as a
    sliding layer and as the full layer make it. With the window of 2,048
    keys the kernel holds two branches: a tile the window binds walks the
    diagonal's block, ``2048 // 512 - 1`` clear blocks written out (no loop:
    four score tiles ``512 x 512`` and their value products) and one chunk of
    64 keys, ten products and masks under two comparisons alone; a tile
    before that the causal form's loop and its diagonal. Both compile for the
    described v5e. Without a window the kernel's module is, operation for
    operation, what the parent of PR 76 lowered (its digest, debug
    information stripped: a line of the file may shift, the kernel may
    not)."""
    import hashlib
    import re

    from storm_tpu.ops.flash_attention import (flash_attention_merged,
                                               window_walk)

    wide, narrow = (_spec((4, 16384, h * 128), jnp.bfloat16, v5e)
                    for h in (32, 4))
    row = _spec((), jnp.int32, v5e)

    def lowered(window):
        return jax.jit(lambda out, q, k, v, i: flash_attention_merged(
            out, q, k, v, i, heads=32, kv_heads=4, scale=128 ** -0.5,
            block_q=64, block_k=512, window=window)).lower(
            wide, wide, narrow, narrow, row)

    assert window_walk(2048, 64, 512) == "tile-end"
    sliding = lowered(2048)
    (kernel,) = _kernel_modules(sliding)
    _, bound, early = kernel.split('"stable_mosaic.scf.if"')
    assert "scf.for" not in bound
    # four blocks' scores, the chunk's, and each one's product with v
    assert sorted(re.findall(r"tpu\.matmul.* -> vector<(\w+)xf32>", bound)) \
        == sorted(4 * ["512x512"] + ["512x64"] + 5 * ["512x128"])
    # the diagonal's mask and the chunk's, and no other
    assert len(re.findall(r"arith\.cmpi.*vector<", bound)) == 2
    assert early.count("scf.for") == 1 and early.count("tpu.matmul") == 4
    assert "tpu_custom_call" in sliding.compile().as_text()

    (kernel,) = _kernel_modules(lowered(None))
    assert hashlib.sha256(kernel.encode()).hexdigest()[:16] == \
        "efccc2616ba9ce19"


def test_the_indexers_selection_compiles_at_the_published_sizes(v5e):
    """``ops/sparse_attention.py select_keys``' kernel as Keye-VL-2.0's cell
    calls it, one row: 16 indexer heads of 64 over 16,384 positions, the top
    2,048 a query. A tile's scores are 8 MB of VMEM beside the row's keys
    (lanes half full) and the mask's block; the scalar test that skips the
    second bisection where no scores tie and the int8 stores are Mosaic's to
    take or refuse. One call, and nothing of ``S x S`` but the mask."""
    from storm_tpu.ops import sparse_attention as sa

    compiled = jax.jit(lambda q, k, w: sa._select_kernel_row(
        q, k, w, topk=2048)).lower(
        _spec((16, 16384, 64), jnp.bfloat16, v5e),
        _spec((16384, 64), jnp.bfloat16, v5e),
        _spec((16384, 16), jnp.float32, v5e)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "s8[1,16384,16384]" in text and "f32[16384,16384]" not in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 16384 * 16384
    assert mem.temp_size_in_bytes < 32 << 20


def test_keyes_mixer_compiles_with_its_rows_unrolled_and_a_mask_at_a_time(
        v5e, monkeypatch):
    """Keye-VL-2.0's mixer whole at its cell's step (4 windows of 16,384 into
    2,048, 32 query heads on 4 key heads of 128, the indexer's 16 heads of
    64, bfloat16) as one chip builds it: ten kernels (the head norm and turn
    of q and of k; a row's selection and its masked second pass, four rows)
    and no loop, so that a trace shows the two passes as events of their own
    under their own parts; and the four 268 MB masks are never all alive (the
    count of squares would keep each until the step's end: 1.34 GiB of
    temporaries as built, 2.1 and more without the barriers)."""
    import re

    import storm_tpu.ops.rope as rope
    import storm_tpu.ops.sparse_attention as sa
    from storm_tpu.models.keye import keye_mixer, keye_mixer_init

    for module in (sa, rope):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert sa.select_form(16384, 64, 2048) == "kernel"
    assert sa.sparse_form(32, 4, 16384, 128, 128, 1) == "kernel"
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: keye_mixer_init(
            jax.random.PRNGKey(0), 2048, 32, 4, 128, 16, 64)))
    x = _spec((4, 16384, 2048), jnp.bfloat16, v5e)
    wide, narrow = (_spec((16384, n), jnp.float32, v5e) for n in (64, 32))
    compiled = jax.jit(lambda p, x, a, b, c, d: keye_mixer(
        p, x, 32, 4, 128, 16, 64, 1e-6, ((a, b), (c, d)), 2048, 512)).lower(
        p, x, wide, wide, narrow, narrow).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 10
    assert not _loops(text)
    assert len(re.findall(r"s8\[1,16384,16384\]\S* custom-call", text)) == 4
    assert "/mix.index_select/" in text and "/mix.sparse_attention/" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * 2 ** 30


@pytest.mark.parametrize("shape,heads", [
    ((256, 257, 1408), 16),   # ViT-g/14, the benchmark's largest bucket
    ((8, 257, 1408), 16),     # and the paced cell's
    ((128, 197, 768), 12),    # ViT-B/16 at batch 128
    ((4, 512, 1024), 8),      # about the longest sequence that still fits
])
def test_short_attention_compiles_for_v5e(v5e, shape, heads):
    """Heads lie at lane offsets that are no multiple of 128 (read in the lane
    tiles that hold them, two tiles folded into one by a select), and the
    token count is no multiple of 8 (the 257th key a column): Mosaic has to
    take both."""
    from storm_tpu.ops.short_attention import _forward, fits

    assert fits(shape[1], shape[2], 2)
    q = _spec(shape, jnp.bfloat16, v5e)
    text = _forward.lower(q, q, q, heads=heads).compile().as_text()
    assert "tpu_custom_call" in text


def test_vit_g14_block_compiles_with_the_row_kernel(v5e, monkeypatch):
    """Two blocks of ViT-g/14 at the backlog cell's bucket of 256, as the chip
    builds them: the kernel is in the program, the transposes XLA made around
    its own attention are not, and the shapes the benchmark's roofline reader
    looks for (``[256,257,1408]``) are still named."""
    import re

    import storm_tpu.ops.attention as attention
    from storm_tpu.models.vit import build_vit

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "_one_device", lambda: True)
    model = build_vit("probe", 1000, (224, 224, 3), patch=14, dim=1408,
                      depth=2, num_heads=16, mlp_dim=6144)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params, state = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e), shapes)
    x = _spec((256, 224, 224, 3), jnp.bfloat16, v5e)
    text = jax.jit(
        lambda p, s, x: model.apply(p, s, x, train=False)[0]
    ).lower(params, state, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "[256,16,257,257]" not in text  # no score tensor in HBM
    found = re.findall(r"\[(\d+),257,1408\]", text)
    assert found and max(set(found), key=found.count) == "256"


def test_vit_g14_compiles_as_one_loop_over_its_stacked_blocks(v5e, monkeypatch):
    """ViT-g/14 whole, forty blocks, from the tree an engine serves (the list
    of blocks and their leaves stacked beside it). At a bucket of 32 rows
    (8,224 tokens: a long step) the program is one ``while`` that carries
    the stream, its row mean and the stacked leaves; no stacked leaf is
    copied; a block's slices are operations of their own, not read inside
    the products; the body's first norm takes the carried row mean, so no
    pass over the carried stream alone is left to make a row sum (the next
    norm's is a second result of ``mlp_out``'s product, as where the blocks
    are unrolled); the text is a twentieth of the unrolled program's 3.6
    million characters, which is what a warm start loads; and the
    benchmark's roofline reader still finds the batch in the operations'
    shapes. At the paced cell's bucket of 8 (2,056 tokens: a short step) the
    same tree gives the unrolled program, which reads the list."""
    import importlib.util
    import json
    import re

    import storm_tpu.ops.attention as attention
    from storm_tpu.models.vit import build_vit, stack_blocks

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sizes = json.load(open(os.path.join(
        root, "benchmarks", "configs", "vit_g14.json")))["published"]
    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    monkeypatch.setattr(attention, "_one_device", lambda: True)
    model = build_vit(
        "probe", sizes["num_labels"], (sizes["image_size"],) * 2 + (3,),
        patch=sizes["patch_size"], dim=sizes["hidden_size"],
        depth=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        mlp_dim=sizes["intermediate_size"])
    shapes, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _spec(a.shape, jnp.bfloat16, v5e),
                          jax.eval_shape(stack_blocks, shapes))
    forward = jax.jit(lambda p, x: model.apply(p, state, x, train=False)[0])
    short = forward.lower(
        params, _spec((8, 224, 224, 3), jnp.bfloat16, v5e)).as_text()
    assert "stablehlo.while" not in short
    text = forward.lower(
        params, _spec((32, 224, 224, 3), jnp.bfloat16, v5e)
    ).compile().as_text()
    assert len(text) < 400_000
    (loop,) = [line for line in text.splitlines() if " while(" in line]
    assert "bf16[40,1408,6144]" in loop and "bf16[32,257,1408]" in loop
    assert "f32[32,257,1]" in loop  # the row mean rides beside the stream
    assert "tpu_custom_call" in text  # the row kernel, inside the body
    assert not re.search(r"= \w+\[40,[\d,]+\]\S* copy\(", text)
    body = re.search(r"body=(%[\w.\-]+)", loop).group(1)
    lines = text[text.index("\n" + body + " "):].split("\n}\n")[0].splitlines()
    # a product (an output fusion) never takes a stacked leaf: its block's
    # slice is cut before it, and then brought into fast memory ahead of it
    products = [line for line in lines if "kind=kOutput" in line]
    assert len(products) >= 5
    carried = {m.group(1) for line in lines for m in [re.match(
        r"\s*(%\S+) = bf16\[40,(?:1408,1408|1408,6144|6144,1408)\]\S* "
        r"get-tuple-element\(", line)] if m}
    assert len(carried) == 6
    for line in products:
        operands = line.split(" fusion(")[1].split(")")[0].split(", ")
        assert not carried & set(operands), line
    stream = {m.group(1) for line in lines for m in [re.match(
        r"\s*(%\S+) = bf16\[32,257,1408\]\S* get-tuple-element\(%\S*arg_tuple",
        line)] if m}
    assert stream
    for line in lines:
        m = re.match(r"\s*%\S+ = f32\[32,257\]\S* fusion\(([^)]*)\), kind=kLoop",
                     line)
        if m:  # a pass that makes a row statistic: never of the stream alone
            assert set(m.group(1).split(", ")) - stream, line
    spec = importlib.util.spec_from_file_location(
        "benchmarks_ops_vit", os.path.join(root, "benchmarks", "ops", "vit.py"))
    ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ops)
    assert ops.rows_per_step(text.splitlines(), sizes) == 32


@pytest.mark.parametrize("chips", [4, 1])
def test_train_step_holds_no_row_kernel(topo, chips, monkeypatch):
    """``parallel/train.py``'s step for one ViT-B/16 block at batch 32 (8.7
    million scores, over the row kernel's threshold), as a TPU host would
    build it (float32, as the repo trains). On four chips (dp 2 x tp 2,
    GSPMD) the process has several devices, so no program of it takes the
    kernel: jax refuses to lower a Mosaic call in a partitioned program, as
    the last lines show. On one chip the forward alone takes it, and the
    differentiated step does not: both passes are jax's own of the jnp path
    (ops/short_attention.py)."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import storm_tpu.ops.attention as attention
    from storm_tpu.models.vit import build_vit
    from storm_tpu.parallel.sharding import tp_param_specs
    from storm_tpu.parallel.train import make_train_step

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    # What jax.device_count() would say in a process on such a host (here it
    # counts the CPU backend's devices).
    monkeypatch.setattr(jax, "device_count", lambda: chips)
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(-1, min(chips, 2)),
                ("data", "model"))
    model = build_vit("probe", 1000, (224, 224, 3), patch=16, dim=768,
                      depth=1, num_heads=12, mlp_dim=3072)
    train_step, opt = make_train_step(model, optax.sgd(1e-3))
    shapes, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a, spec: _spec(a.shape, a.dtype, NamedSharding(mesh, spec)),
        shapes, tp_param_specs(shapes))
    opt_state = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, NamedSharding(mesh, P())),
        jax.eval_shape(opt.init, params))
    rows = NamedSharding(mesh, P("data"))
    x = _spec((32, 224, 224, 3), jnp.float32, rows)
    y = _spec((32,), jnp.int32, rows)

    def forward(p, s, x):
        return model.apply(p, s, x, train=False)[0]

    text = jax.jit(forward).lower(params, state, x).compile().as_text()
    assert ("tpu_custom_call" in text) == (chips == 1)
    # The kernel would show in the lowered text already; compiling the step
    # takes half a minute and adds nothing to that.
    step = train_step.lower(params, opt_state, state, x, y).as_text()
    assert "tpu_custom_call" not in step
    if chips > 1:
        assert "all-reduce" in text  # the program is split, not replicated
        monkeypatch.setattr(attention, "_one_device", lambda: True)
        with pytest.raises(NotImplementedError, match="partitioned"):
            # a new function: jit keeps the trace of ``forward``
            jax.jit(lambda *a: forward(*a)).lower(params, state, x)


def test_w8a16_matmul_compiles_for_v5e(v5e):
    """vit_b16's mlp_in (768 x 3072) at batch 64, int8 weights."""
    from storm_tpu.ops.quant_matmul import w8a16_matmul

    x = _spec((12608, 768), jnp.bfloat16, v5e)
    q = _spec((768, 3072), jnp.int8, v5e)
    s = _spec((3072,), jnp.float32, v5e)
    text = w8a16_matmul.lower(x, q, s).compile().as_text()
    assert "tpu_custom_call" in text


def test_longseq_encoder_forward_compiles_with_flash_kernel(v5e, monkeypatch):
    """The whole longseq_encoder forward at batch 8 with the kernel in it.
    On a CPU host the dispatch predicate answers False, so the kernel is
    forced on here, in the test (not through an option of the program)."""
    import storm_tpu.ops.attention as attention
    from storm_tpu.models.registry import build_model

    monkeypatch.setattr(attention, "_use_pallas", lambda: True)
    model = build_model("longseq_encoder", num_classes=10,
                        input_shape=(2048, 64))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params, state = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16 if a.dtype == jnp.float32
                        else a.dtype, v5e), shapes)

    def fwd(p, s, x):
        logits, _ = model.apply(p, s, x, train=False)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    x = _spec((8, 2048, 64), jnp.bfloat16, v5e)
    compiled = jax.jit(fwd).lower(params, state, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # fits one chip's 16 GB with room to spare
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 << 30


def _loops_text(text, carried):
    """The digest of what the ``while`` loops that carry ``carried`` run, in
    a compiled program's text: each loop's body and condition and every
    computation those call, in the order met, less the metadata, with each
    name's counter replaced by its order of appearance (a counter shifts
    with whatever else the program holds)."""
    import hashlib
    import re

    held, name, computations = [], None, {}
    for line in text.splitlines():
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        if line.endswith("{") and " -> " in line:
            name, held = line.split(" ")[0].lstrip("%"), [line]
        elif name:
            held.append(line)
            if line == "}":
                computations[name], name = "\n".join(held), None
    entry = next(body for body in computations.values() if any(
        " while(" in line and carried in line.split(" while(")[0]
        for line in body.splitlines()))
    todo = [m for line in entry.splitlines() if " while(" in line
            and carried in line.split(" while(")[0]
            for m in re.findall(r"(?:condition|body)=%([\w.\-]+)", line)]
    assert todo
    met = []
    while todo:
        name = todo.pop(0)
        if name in met:
            continue
        met.append(name)
        todo += re.findall(r"(?:calls|to_apply|condition|body)=%([\w.\-]+)",
                           computations[name])
    seen = {}
    whole = re.sub(
        r"%?([A-Za-z_][\w\-]*?)((?:\.\d+|\.clone|\.sunk|\.\.sunk)+)\b",
        lambda m: seen.setdefault(m.group(0), f"{m.group(1)}#{len(seen)}"),
        "\n".join(computations[name] for name in met))
    return hashlib.sha256(whole.encode()).hexdigest()[:16]


# the combine's loop where the whole router is held (Trinity's and Keye's:
# one text, 65,536 tokens of 2,048 channels in blocks of 64), as the parent
# of PR 69 compiled it (:func:`_loops_text` of the loop that carries the
# sums): that PR changed the loops of the layers that hold a part, and left
# this one
ONCE = "cafbd7e975efe871"


@pytest.mark.parametrize(
    "n,dim,top_k,held,width,hidden,form,tile,stacked,sums,matmul_ms,"
    "combine_ms,smalls", [
        (32768, 2304, 8, 32, 256, 1024, "swiglu", 1024,  # Kimi-Linear's cell
         "bf16[32,2304,1024]", "f32[32768,2304]",
         "expert_matmul_ms", "expert_combine_ms", (512, 384)),
        (32768, 2688, 6, 32, 128, 1856, "relu2", 1024,  # Nemotron's cell
         "bf16[32,2688,1856]", "f32[32768,2688]",
         "relu2_expert_matmul_ms", "expert_combine_ms", (512,)),
        (16384, 7168, 8, 12, 384, 2048, "swiglu", 512,  # Kimi K2's cell
         "bf16[12,7168,2048]", "f32[16384,7168]",
         "k2_expert_matmul_ms", "k2_expert_combine_ms", (384, 128)),
        (32768, 4096, 8, 40, 320, 1280, "swiglu", 512,  # Solar's cell
         "bf16[40,4096,1280]", "f32[32768,4096]",
         "solar_expert_matmul_ms", "solar_expert_combine_ms", (256, 384)),
        (65536, 2048, 8, 128, 128, 1024, "swiglu", 1024,  # Trinity's cell
         "bf16[128,2048,1024]", "f32[65536,2048]",
         "trinity_expert_matmul_ms", "trinity_expert_combine_ms", (512,)),
        (65536, 2048, 8, 128, 128, 768, "swiglu", 1024,  # Keye's cell, whose
         "bf16[128,2048,768]", "f32[65536,2048]",  # metrics read the parts
         None, None, (512,)),
    ], ids=["kimi_linear_48b", "nemotron_3_nano_30b", "kimi_k2_6",
            "solar_open2_250b", "trinity_mini", "keye_vl2_30b"])
def test_expert_layer_compiles_with_the_loops_the_metrics_look_for(
        v5e, n, dim, top_k, held, width, hidden, form, tile, stacked, sums,
        matmul_ms, combine_ms, smalls):
    """The language cells' expert layer at their real sizes, shapes only,
    its tile the one the layer takes from those shapes (``tile``: 1,024 rows
    where a held expert's expected run fills one, 512 below; no caller names
    it): the tile loops still carry the experts' stacked weights and the
    combine's loops the tokens' float32 sums (the benchmark's
    ``*expert_matmul_ms`` and ``*expert_combine_ms`` find the loops in a
    trace by those shapes and add up what they find, and a listed metric
    that reads nothing makes a run malformed), no loop both: exactly one
    loop a size, two sizes a loop at most (the tile and ``smalls``' one, the
    experts' then the combine's, which on Nemotron has none: a body is a
    copy of the loop's work in the program, a layer, and costs its share of
    every load), and no ``conditional`` anywhere (one whose branches
    hold the matrix products cost 13-29 us a tile on the chip). Where the
    whole router is held (Trinity's, Keye's) the combine's block is 64
    tokens, one tile of 512 each, and its loop has one size and reads no
    sums; the four that hold a part keep 256 tokens and their ``smalls``,
    and have one loop more: those of their sizes write a block's first tile
    and read no sums either, and the last, of the whole size, reads a
    block's sums back and adds a further tile to them. The sums are
    allocated in all six: no ``broadcast`` fills them. Around them no
    scatter, no gather of a value an assignment (``route_topk``'s chosen
    scores are a comparison reduced inside one fusion: no ``[tokens, top_k,
    width]`` array leaves one), and the tiles' buffer is allocated with its
    last row zeroed in place, never filled, and neither it nor the sums nor
    an expert's matrices are copied."""
    import math
    import re

    from storm_tpu.parallel import moe

    p = jax.eval_shape(lambda: moe.topk_moe_init(
        jax.random.PRNGKey(0), dim, hidden, width, held, form=form))
    p = jax.tree.map(lambda a: _spec(a.shape, jnp.bfloat16, v5e), p)
    x = _spec((n, dim), jnp.float32, v5e)
    assert moe.run_tile(n * top_k / width) == tile
    text = jax.jit(lambda p, x: moe.topk_moe_layer(
        p, x, top_k)).lower(p, x).compile().as_text()
    lines = [re.sub(r"\{[^}]*\}", "", line) for line in text.splitlines()]
    loops = [line for line in lines if " while(" in line]
    combines = len(smalls) + (held != width)  # a part held: the adding loop
    assert sum(stacked in line for line in loops) == 2
    assert sum(sums in line for line in loops) == combines
    assert not any(stacked in line and sums in line for line in loops)
    # and as a trace names them, by the benchmark's own patterns
    for metric, found in ((matmul_ms, 2), (combine_ms, combines)):
        if metric is None:  # read by the part's name, not by a shape
            continue
        assert sum(bool(re.search(_metric_pattern(metric), loop))
                   for loop in _loops(text)) == found, metric
    assert " conditional(" not in text
    assert "scatter" not in text
    # how many slices a gather fetches: a tile's rows at each of its sizes
    # (512 at a time where it has more: ``_GATHER_ROWS``) or a bisection's
    # probes, nowhere one an assignment
    fetched = set()
    for line in text.splitlines():
        found = re.search(r" = \w+\[([\d,]+)\]\S* gather\(.*"
                          r"offset_dims=\{([\d,]*)\}", line)
        if found:
            dims = found.group(1).split(",")
            offsets = set(found.group(2).split(","))
            fetched.add(math.prod(
                int(d) for i, d in enumerate(dims) if str(i) not in offsets))
    # the combine's block and tile by the rule: the most tokens, 256 at
    # most, whose expected held assignments fit 512 rows
    block = min(256, 512 * width // (top_k * held) // 8 * 8)
    combined = min(512, block * top_k)
    assert (block, combined) == ((64, 512) if held == width else (256, 512))
    tiles = {-(-n * top_k // tile) + held,
             -(-n * top_k // combined) + n // block}
    rows = {held + 1, n // block + 1, min(tile, moe._GATHER_ROWS),
            combined} | set(smalls)  # a tile's tokens 512 rows a gather
    assert rows <= fetched <= rows | tiles  # every tile's run, to part them
    # what an operation outside a fusion's body writes is an array in memory
    written, fused = [], False
    for line in lines:
        if line.endswith("{") and "->" in line:
            fused = line.startswith("%fused_computation")
        elif not fused and " = " in line:
            written.append(line)
    assert not any(f"[{n},{top_k},{width}]" in line.split(" = ")[1].split(
        "(")[0] for line in written)
    # the buffer is written where it lies: by the zero row's fusion, of no
    # operand (no ``broadcast`` fills it), and once in each size's loop; the
    # sums are allocated and no more (every block is written, whatever is
    # held) and written once in each loop of the combine: where the whole
    # router is held by a ``dynamic-update-slice`` at a looked-up row behind
    # the product, where a part is held by the product's own fusion, which
    # sees the sums a block a page (``pages``: a ``bitcast``) and writes its
    # page. No ``copy`` of any: not from one loop to the next, not on to the
    # combine; and none of an expert's matrices into fast memory
    buffer = "bf16[%d,%d]" % ((-(-n * top_k // tile) + held) * tile + 1, dim)
    pages = "f32[%d,%d,%d]" % (n // block, block, dim)
    for array, sizes in ((buffer, 2), (sums, combines * (held == width)),
                         (pages, combines * (held != width) - 1)):
        makes = [line for line in written if line.split(" = ")[1].startswith(
            array) and not any(f" {op}(" in line for op in (
                "get-tuple-element", "parameter", "bitcast"))]
        assert not any(" copy(" in line for line in makes), makes
        assert all(" fusion(" in line or " dynamic-update-slice(" in line
                   or "AllocateBuffer" in line for line in makes), makes
        assert len(makes) == sizes + 1, makes
        assert array != pages or all(
            "dynamic-update-slice_fusion" in line for line in makes)
    assert sum(sums in line and 'custom_call_target="AllocateBuffer"' in line
               for line in text.splitlines()) == 1
    if held == width:
        assert _loops_text(text, sums) == ONCE
    # only the adding loop reads a block of the sums back: one fusion takes
    # the pages and gives one (which the product's adds to what it makes),
    # where a part is held, and nothing outside a fusion slices them
    reads = [line for line in lines if line.startswith("%fused_computation")
             and f": {pages}" in line.split(" -> ")[0]
             and line.split(" -> ")[1].startswith(f"f32[1,{block},{dim}]")]
    assert len(reads) == (held != width), reads
    assert not any(" dynamic-slice(" in line and (sums in line or pages in line)
                   for line in written)
    assert not any(re.search(
        r" = bf16\[(%d,%d|%d,%d)\]\S* copy\(" % (dim, hidden, hidden, dim),
        line) for line in text.splitlines())
    assert sum(" fusion()" in line and "dynamic-update-slice" in line
               and line.split(" = ")[1].startswith(buffer)
               for line in written) == 1
    assert sum(buffer in line and 'custom_call_target="AllocateBuffer"' in
               line for line in text.splitlines()) == 1


def test_granites_expert_layer_compiles_with_tiles_of_1024(v5e):
    """Granite's expert layer at its cell's step, 32,768 tokens of 4,096
    channels, ten experts a token of a router of 72, the first 36 held at a
    width of 768: a held expert's expected run is 4,551 rows, so the layer
    cuts tiles of 1,024 and a run's last at 512: two loops that carry the
    experts' stacked weights (its metrics read the part ``moe.experts``,
    whatever is under it), the tiles' buffer of the worst case at that tile,
    356 tiles and the zero row, allocated once and never filled or copied,
    and every gather of tokens 512 rows. The combine holds half of the
    router, in blocks of 96 tokens (480 +- 15 held assignments of a tile's
    512): the 342 blocks' sums are allocated, no ``broadcast`` fills them,
    and two loops carry them with no ``copy`` between: one over the blocks,
    whose body writes a block's first tile and slices nothing out of the
    sums, and one over the further tiles of the few blocks that pass 512,
    which reads the block back and adds; in both the product's fusion writes
    its block itself, a page of the sums seen ``[342, 96, 4096]``."""
    import re

    from storm_tpu.ops.platform import dispatch_notes
    from storm_tpu.parallel import moe

    n, dim, top_k, held, width, hidden = 32768, 4096, 10, 36, 72, 768
    p = jax.eval_shape(lambda: moe.topk_moe_init(
        jax.random.PRNGKey(0), dim, hidden, width, held,
        shared_hidden=2 * hidden, selection_bias=False))
    p = jax.tree.map(lambda a: _spec(a.shape, jnp.bfloat16, v5e), p)
    x = _spec((8, 4096, dim), jnp.float32, v5e)
    with dispatch_notes() as seen:
        text = jax.jit(lambda p, x: moe.topk_moe_layer(
            p, x, top_k, router="softmax")).lower(p, x).compile().as_text()
    assert {"expert_tiles=last-512", "combine_tiles=whole",
            "combine_write=first"} <= set(seen)
    loops = [re.sub(r"\{[^}]*\}", "", line) for line in _loops(text)]
    assert sum("bf16[36,4096,768]" in line for line in loops) == 2
    sums = "f32[32832,4096]"  # 342 x 96
    carry = [line for line in loops if sums in line]
    assert len(carry) == 2
    # the writing loop looks its 342 blocks up, the adding one 640 tiles at
    # most (327,680 assignments over 512), and neither the other's
    assert ["s32[342]" in line for line in carry] == [True, False]
    assert ["s32[640]" in line for line in carry] == [False, True]
    lines = [re.sub(r"\{[^}]*\}", "", line) for line in text.splitlines()]
    assert sum(sums in line.split(" custom-call(")[0]
               and 'custom_call_target="AllocateBuffer"' in line
               for line in text.splitlines()) == 1
    assert not any(re.search(r" = f32\[32832,4096\]\S* (copy|broadcast)\(",
                             line) for line in text.splitlines())
    # both loops' products write their block themselves, a page of the sums
    # (a view of them: no ``dynamic-update-slice`` stands behind a product),
    # and one fusion more takes the pages and gives one: the adding loop's
    # read of a block, which its product adds to; the writing loop reads none
    pages = "f32[342,96,4096]"
    assert sum(" = " + pages + " fusion(" in line
               and "dynamic-update-slice_fusion" in line
               for line in lines) == 2
    assert not any(" dynamic-update-slice(" in line and sums in line
                   for line in lines if not line.lstrip().startswith("ROOT"))
    reads = [line for line in lines if line.startswith("%fused_computation")
             and f": {pages}" in line.split(" -> ")[0]
             and line.split(" -> ")[1].startswith("f32[1,96,4096]")]
    assert len(reads) == 1, reads
    assert not any(" dynamic-slice(" in line and (sums in line or pages
                                                   in line)
                   for line in lines if not line.startswith("%"))
    buffer = "bf16[%d,4096]" % ((-(-n * top_k // 1024) + held) * 1024 + 1)
    assert buffer == "bf16[364545,4096]"
    assert sum(buffer in line and 'custom_call_target="AllocateBuffer"' in
               line for line in text.splitlines()) == 1
    assert not any(re.search(r" = bf16\[364545,4096\]\S* (copy|broadcast)\(",
                             line) for line in text.splitlines())
    assert " conditional(" not in text and "scatter" not in text
    # a tile's tokens 512 rows a gather (two of them cost less than one of
    # 1,024 rows): two in the loop of 1,024, one in the loop of 512, and the
    # combine's two loops' one of 512 each
    assert len(re.findall(r" = bf16\[512,4096\]\S* gather\(", text)) == 5
    assert not re.search(r" = bf16\[1024,4096\]\S* gather\(", text)


def _metric_pattern(name):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "metrics", name + ".json")) as f:
        return json.load(f)["args"]["pattern"]


def _loops(text):
    """A compiled program's ``while`` operations as a trace names them."""
    return [line.strip().removeprefix("ROOT ") for line in text.splitlines()
            if " while(" in line]


def test_sparse_attention_compiles_as_the_loops_the_metrics_look_for(
        v5e, monkeypatch):
    """``minicpm_sala``'s attention at its cell's step, 4 windows of 16,384
    with 32 query heads over 2 key heads of 128, as one chip builds it: the
    mask kernel is in the program, the second pass is one ``while`` that
    carries the rows' indices, the selection and q where they lie (the
    benchmark's ``sparse_attention_ms`` finds it so), and the first pass is
    another that the pattern does not take for it."""
    import re

    import storm_tpu.ops.sparse_attention as sa
    from storm_tpu.models.minicpm_sala import INFLLM_V2

    monkeypatch.setattr(sa, "_use_pallas", lambda: True)
    monkeypatch.setattr(sa, "_one_device", lambda: True)
    assert sa.sparse_form(32, 2, 16384, 128, 128, 64) == "kernel"
    assert sa.sparse_form(32, 2, 16384 + 64, 128, 128, 64) == "blocked"
    q, k = (_spec((4, h, 16384, 128), jnp.bfloat16, v5e) for h in (32, 2))
    text = jax.jit(lambda q, k, v: sa.block_sparse_attention(
        q, k, v, 128 ** -0.5, **INFLLM_V2)).lower(q, k, k).compile().as_text()
    assert "tpu_custom_call" in text
    wanted = re.compile(_metric_pattern("sparse_attention_ms"))
    found = [bool(wanted.search(line)) for line in _loops(text)]
    assert len(found) == 2 and sum(found) == 1
    (second,) = [line for line in _loops(text) if wanted.search(line)]
    assert "bf16[4,32,16384,128]" in second and "s32[4]" in second
    # the picked blocks' mask a row (int8, 537 MB) is the largest thing made
    assert "f32[32,16384," not in text and "f32[2,16,16384," not in text


def test_lightning_scan_compiles_to_the_loop_its_metric_looks_for(v5e):
    """``ssd_chunked`` as ``minicpm_sala``'s lightning layers call it (a
    group a head, 32 states of 128 x 128 a row): one ``while`` that carries
    ``f32[4,32,1,128,128]``, which ``lightning_scan_ms`` finds and Nemotron's
    ``ssd_scan_ms`` does not."""
    import re

    from storm_tpu.ops.ssd import ssd_chunked

    x = _spec((4, 16384, 32, 128), jnp.bfloat16, v5e)
    dt = _spec((4, 16384, 32), jnp.float32, v5e)
    a = _spec((32,), jnp.float32, v5e)
    (loop,) = _loops(jax.jit(lambda x, dt, a, b, c, d: ssd_chunked(
        x, dt, a, b, c, d, chunk=128)).lower(x, dt, a, x, x, a)
        .compile().as_text())
    assert "f32[4,32,1,128,128]" in loop
    assert re.search(_metric_pattern("lightning_scan_ms"), loop)
    assert not re.search(_metric_pattern("ssd_scan_ms"), loop)


def _mamba_mixer_at_its_step(v5e):
    """Nemotron's Mamba-2 mixer: 8 windows of 4,096 into 2,688, 64 heads of
    64 over 8 groups of 128, bfloat16."""
    from storm_tpu.models import nemotron_h as N

    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: N.mamba_mixer_init(
            jax.random.PRNGKey(0), 2688, 64, 64, 8, 128, 4)))
    x = _spec((8, 4096, 2688), jnp.bfloat16, v5e)
    return jax.jit(lambda p, x: N.mamba_mixer(
        p, x, 64, 64, 8, 128, 128, 1e-5)).lower(p, x)


def _lightning_mixer_at_its_step(v5e):
    """MiniCPM-SALA's lightning mixer: 4 windows of 16,384 into 4,096, 32
    heads of 128, bfloat16."""
    from storm_tpu.models import minicpm_sala as M

    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: M.lightning_mixer_init(
            jax.random.PRNGKey(0), 4096, 32, 128)))
    x = _spec((4, 16384, 4096), jnp.bfloat16, v5e)
    turn = _spec((16384, 64), jnp.float32, v5e)
    slopes = M.lightning_slopes(32, 1, 32)
    return jax.jit(lambda p, x, cos, sin: M.lightning_mixer(
        p, x, 32, 128, 1e-6, (cos, sin), slopes, 128)).lower(
        p, x, turn, turn)


@pytest.mark.parametrize("lowered,metric,other,held", [
    (_mamba_mixer_at_its_step, "ssd_scan_ms", "lightning_scan_ms",
     "bf16[8,4096,6144]"),
    (_lightning_mixer_at_its_step, "lightning_scan_ms", "ssd_scan_ms",
     "bf16[4,16384,4096]"),
], ids=["mamba_mixer-nemotron", "lightning_mixer-minicpm_sala"])
def test_scan_reads_and_writes_where_its_mixer_holds_them(
        v5e, monkeypatch, lowered, metric, other, held):
    """Both callers of ``ssd_chunked`` at their cells' steps, as one chip
    builds them: the scan is one ``while`` that its cell's metric finds (and
    the other cell's does not) and that carries its operands position-major
    as the mixer holds them (Nemotron's the convolution's whole result, ``x
    | B | C``) and its result beside them; nothing is brought chunk-first for
    it, its result is widened or re-tiled by no pass of its own on the way
    to the norm, nothing is sliced out of the convolution's result, and
    nothing is scattered. (The float32 arrays of a branch's size that stay
    are the norms' own, made inside the projections' fusions: the gate's
    product and the head norms' inputs.)"""
    import re

    from storm_tpu.ops import kda

    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(kda, "_one_device", lambda: True)
    text = lowered(v5e).compile().as_text()
    (loop,) = _loops(text)
    assert re.search(_metric_pattern(metric), loop)
    assert not re.search(_metric_pattern(other), loop)
    assert held in loop and "scatter(" not in text
    # chunk-first, as ``lax.scan`` cut its operands and stacked its result
    assert not re.search(
        r"\[(32,8|8,32),128,8,8,64\]|\[(128,4|4,128),128,32,1,128\]", text)
    entry = text[text.index("ENTRY"):]
    branch = r"(8,4096,4096|4,16384,4096|8,4096,6144)"
    assert not re.search(r"= \w+\[" + branch + r"\]\S* "
                         r"(copy|reshape|transpose|convert|slice)\(", entry)


def test_granites_scan_keeps_its_state_in_fast_memory(v5e, monkeypatch):
    """Granite's Mamba-2 mixer at its cell's step (8 windows of 4,096 into
    4,096, 128 heads of 64 on one group of 128, bfloat16): 8 rows' state is
    32 MiB, which the compiler leaves in HBM where a loop carries it, so as
    one chip builds it the scan is one kernel under ``mix.ssd_scan``
    (``ops/ssd.py scan_form``: a row and 64 heads a step, their state in
    VMEM scratch) and no ``while`` is left to carry a state anywhere. The
    convolution's whole result ``x | B | C`` is read where the mixer holds
    it, position-major, and the result written so; outside the scan nothing
    of a branch's size is copied, re-tiled, widened or sliced, and nothing
    is scattered."""
    import re

    from storm_tpu.models import nemotron_h as N
    from storm_tpu.ops import kda, ssd

    for module in (kda, ssd):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert ssd.scan_form(8, 4096, 128, 64, 1, 128, 128) == (
        "kernel-rows1-heads64")
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: N.mamba_mixer_init(
            jax.random.PRNGKey(0), 4096, 128, 64, 1, 128, 4)))
    x = _spec((8, 4096, 4096), jnp.bfloat16, v5e)
    text = jax.jit(lambda p, x: N.mamba_mixer(
        p, x, 128, 64, 1, 128, 128, 1e-5)).lower(p, x).compile().as_text()
    assert not _loops(text)
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "mix.ssd_scan" in line]
    assert re.search(r"= bf16\[8,4096,8192\]\{2,1,0:", call)
    assert call.count("bf16[8,4096,8448]{2,1,0}") == 3  # x, B and C
    assert "scatter(" not in text
    entry = text[text.index("ENTRY"):]
    assert not re.search(r"= \w+\[8,4096,(8576|8448|8192|4096)\]\S* "
                         r"(copy|reshape|transpose|convert|slice)\(", entry)


@pytest.mark.parametrize("chunk,heads", [(128, 64), (256, 32)])
def test_granites_scan_kernel_fits_fast_memory_at_both_chunks(
        v5e, monkeypatch, chunk, heads):
    """The scan alone at the cell's 8 rows as the benchmark's mixer check
    times it, at the chunk that ships and at the published 256: the kernel
    compiles at both (64 heads a step at a chunk of 256 ran out of VMEM on
    the chip, PR 64: a longer chunk takes fewer heads a step)."""
    from storm_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    monkeypatch.setattr(ssd, "_one_device", lambda: True)
    assert ssd.scan_form(8, 4096, 128, 64, 1, 128, chunk) == (
        f"kernel-rows1-heads{heads}")
    xbc = _spec((8, 4096, 8448), jnp.bfloat16, v5e)
    dt = _spec((8, 4096, 128), jnp.float32, v5e)
    a = _spec((128,), jnp.float32, v5e)
    text = jax.jit(lambda xbc, dt, a, d: ssd.ssd_chunked_columns(
        xbc, dt, a, d, 1, 128, chunk)).lower(xbc, dt, a, a).compile().as_text()
    assert "tpu_custom_call" in text and not _loops(text)


def test_kda_mixer_compiles_with_every_branch_in_lanes(v5e, monkeypatch):
    """Kimi-Linear's KDA mixer at its cell's step (8 windows of 4,096 into
    2,304, 32 heads of 128, bfloat16) as one chip builds it: the tables'
    kernel is in the program, both loops carry the 64 x 64 table
    ``kda_scan_ms`` finds them by, and outside the loops nothing of a
    branch's size has heads for an axis (``[.., 32, 128]`` puts eight heads
    in a tile where ``[.., 4096]`` puts eight positions: each way between
    them re-tiles the whole array) nor is re-tiled by a ``reshape``: the one
    pass left is the copy that brings the chain's result to lanes, in
    bfloat16."""
    import re

    from storm_tpu.models import kimi_linear as K
    from storm_tpu.ops import kda

    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(kda, "_one_device", lambda: True)
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: K.kda_mixer_init(
            jax.random.PRNGKey(0), 2304, 32, 128, 4)))
    x = _spec((8, 4096, 2304), jnp.bfloat16, v5e)
    text = jax.jit(lambda p, x: K.kda_mixer(p, x, 32, 128, 64, 1e-5)).lower(
        p, x).compile().as_text()
    assert "tpu_custom_call" in text
    wanted = re.compile(_metric_pattern("kda_scan_ms"))
    assert [bool(wanted.search(line)) for line in _loops(text)] == [True] * 2
    entry = text[text.index("ENTRY"):]
    assert not re.search(r"\[8,4096,32,128\]|\[4096,8,32,128\]", entry)
    assert not re.search(r"\[8,4096,4096\]\S* reshape\(", entry)
    # (the compiler spells that array ``[64,8,32,8,8,128]`` or, with the
    # convolutions a kernel's calls, ``[8,64,8,8,32,128]``)
    assert re.findall(r"= (\w+)\[\d+(?:,\d+){4,}\]\S* copy\(",
                      entry) == ["bf16"]


def test_kda_mixer_compiles_at_64_heads_with_steps_past_one(v5e, monkeypatch):
    """The same mixer as Solar Open 2's plan calls it, at its cell's step (8
    windows of 4,096 into 4,096, 64 heads of 128, the step in (0, 2)): the
    tables' kernel is in the program at twice Kimi-Linear's heads and lanes
    (8,192 channels a branch: 64 lane tiles for the convolution's kernel
    too), both loops carry the table ``solar_kda_scan_ms`` finds them by, and
    the hand-over is Kimi-Linear's: no array of a branch's size with heads
    for an axis outside the loops, one copy on the way out, in bfloat16."""
    import re

    from storm_tpu.models import kimi_linear as K
    from storm_tpu.ops import kda

    monkeypatch.setattr(kda, "_use_pallas", lambda: True)
    monkeypatch.setattr(kda, "_one_device", lambda: True)
    assert kda.tables_form(128, 128, 64) == "kernel"
    assert kda.conv_form(8192, 4096, 4) == "kernel"
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: K.kda_mixer_init(
            jax.random.PRNGKey(0), 4096, 64, 128, 4)))
    x = _spec((8, 4096, 4096), jnp.bfloat16, v5e)
    compiled = jax.jit(lambda p, x: K.kda_mixer(
        p, x, 64, 128, 64, 1e-5, step_range=2.0)).lower(p, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2  # the tables, the convolutions
    wanted = re.compile(_metric_pattern("solar_kda_scan_ms"))
    assert [bool(wanted.search(line)) for line in _loops(text)] == [True] * 2
    entry = text[text.index("ENTRY"):]
    assert not re.search(r"\[8,4096,64,128\]|\[4096,8,64,128\]", entry)
    assert not re.search(r"\[8,4096,8192\]\S* reshape\(", entry)
    assert re.findall(r"= (\w+)\[\d+(?:,\d+){4,}\]\S* copy\(",
                      entry) == ["bf16"]
    # this mixer's temporaries are most of the step's (of its 6.8 GB: the
    # tables of 8 rows of 64 heads, the decay in float32)
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2 ** 30


def test_eva_mixer_compiles_with_the_heads_merged_and_its_loops_found(
        v5e, monkeypatch):
    """EvaByte's mixer at its cell's step (4 windows of 16,384 into 4,096,
    32 heads of 128, windows of 2,048 in chunks of 16, bfloat16) as one chip
    builds it: three kernels (the turn in lanes, the summaries, the
    attention), every array of a window's length ``(4, 16384, 4096)`` (no
    view a head is ever made: each is a copy of half a gigabyte), and the
    two loops over rows are what ``eva_chunks_roofline_share`` and
    ``eva_attention_roofline_share`` look for, each its own and neither the
    other's. The attention's loop carries its operands as the mixer made
    them, 1,024 summaries and 16,384 positions a row and nothing padded (two
    whole summary blocks of 512: PR 53); the metric's pattern holds the same
    two shapes, and the line below holds them without it."""
    import re

    import storm_tpu.ops.eva_attention as ea
    import storm_tpu.ops.rope as rope
    from storm_tpu.models.evabyte import eva_mixer

    for module in (ea, rope):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert ea.eva_form(16384, 128, 2048, 16) == "kernel"
    assert ea.eva_form(16384 + 256, 128, 2048, 16) == "blocked"
    assert ea.chunks_form(16384, 128, 16) == "kernel"
    assert ea.chunks_form(16384, 64, 16) == "xla"
    assert rope.turn_form(16384, 128) == "lanes"
    assert rope.turn_form(16384, 64) == "halves"
    square = _spec((4096, 4096), jnp.bfloat16, v5e)
    pooling = _spec((32, 128), jnp.bfloat16, v5e)
    p = {"q": square, "k": square, "v": square, "o": square, "mu": pooling,
         "phi": pooling}
    x = _spec((4, 16384, 4096), jnp.bfloat16, v5e)
    tables = _spec((16384, 64), jnp.float32, v5e)
    text = jax.jit(lambda p, x, cos, sin: eva_mixer(
        p, x, 32, 128, 2048, 16, (cos, sin))).lower(
        p, x, tables, tables).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert not re.search(r"\[4,16384,32,\d+\]", text)
    loops = _loops(text)
    assert len(loops) == 2
    chunks, attention = (re.compile(_metric_pattern(
        f"eva_{kind}_roofline_share")) for kind in ("chunks", "attention"))
    assert [bool(chunks.search(line)) for line in loops] == [True, False]
    assert [bool(attention.search(line)) for line in loops] == [False, True]
    assert all(carried in loops[1] for carried in (
        "s32[4]", "bf16[4,1024,4096]", "bf16[4,16384,4096]"))
    # a partial last window of whole tiles is the kernel's still (its keys
    # are read as a whole window's, its 960 summaries as two whole blocks of
    # 512: ``_kernel_row`` pads both); positions short of a tile are XLA's
    assert ea.eva_form(16384 - 1024, 128, 2048, 16) == "kernel"
    assert ea.eva_form(16384 - 1000, 128, 2048, 8) == "blocked"


@pytest.mark.parametrize("s,summaries", [(16384, 1024), (16384 - 1024, 960),
                                         (2048, 128)])
def test_eva_attention_kernel_compiles_where_summaries_are_not_whole_blocks(
        v5e, s, summaries):
    """A row of the attention's kernel at the cell's widths: the cell's own
    sequence (two whole summary blocks, nothing padded), one whose last
    window is partial (960 summaries, padded to 1,024 beside the keys) and a
    single window (128 summaries that no tile reads, padded to one block)."""
    import storm_tpu.ops.eva_attention as ea

    q = _spec((2, s, 4096), jnp.bfloat16, v5e)
    kbar = _spec((2, summaries, 4096), jnp.bfloat16, v5e)
    text = ea._kernel_row.lower(
        q, q, q, kbar, kbar, _spec((), jnp.int32, v5e), heads=32,
        window=2048, chunk=16, scale=128 ** -0.5).compile().as_text()
    assert "tpu_custom_call" in text
    assert (" pad(" in text) == (summaries % ea.eva_tiles(2048, 16)[2] != 0)


def test_falcon_h1s_feed_forward_makes_its_product_once_on_the_way_out_of_a_product(
        v5e):
    """Falcon-H1's feed-forward at its cell's step, 4 rows of 16,384 x 5,120
    into 21,504, a row a trip of one loop, as one chip builds it. The
    placement, not a time: the fusion that holds the down product takes the
    rounded product of gate and up as its one operand of a row's width (the
    parent's took the gate's and the up's results, both, and made the
    float32 scalar, SiLU and product again for every tile of its columns:
    estimated at 42.53 M cycles beside 32.60 M a bare product, 107.7 M a
    row); of the two products before it, whichever the compiler runs second
    takes the first's result and writes that operand, every element once
    (36.41 M, and 32.34 M for the down product that is now bare: 101.4 M a
    row); the first takes nothing of a row's width. A row holds two results
    of 0.70 GB at most, as before, and the product may stand where the
    first's result stood."""
    import re

    from storm_tpu.models.falcon_h1 import gated_ffn
    from storm_tpu.models.minicpm_sala import _rows
    from storm_tpu.ops.platform import dispatch_notes

    rows, seq, dim, width = 4, 16384, 5120, 21504
    p = {"gate": _spec((dim, width), jnp.bfloat16, v5e),
         "up": _spec((dim, width), jnp.bfloat16, v5e),
         "down": _spec((width, dim), jnp.bfloat16, v5e)}
    x = _spec((rows, seq, dim), jnp.bfloat16, v5e)
    with dispatch_notes() as seen:
        compiled = jax.jit(lambda p, x: _rows(
            lambda row: gated_ffn(p, row, 0.5), x)).lower(p, x).compile()
    assert seen == ["gated_ffn=made-once"]
    text = compiled.as_text()
    assert len(_loops(text)) == 1
    wide = re.compile(r"bf16\[(?:1,)*%d,%d\]" % (seq, width))
    # every fused computation that holds a product: how many of its
    # parameters are a row's width, and whether its result is
    products = [(len(wide.findall(params)), bool(wide.fullmatch(result)))
                for params, result, body in re.findall(
                    r"^%\S+ \(([^\n]*)\) -> (\S+) \{\n(.*?)^\}", text,
                    re.M | re.S) if " convolution(" in body or " dot(" in body]
    # (0, True): one of gate and up, bare; (1, True): the other, which takes
    # the first's result and writes the product; (1, False): the down
    # product, which takes the product alone
    assert sorted(products) == [(0, True), (1, False), (1, True)]
    # the first's result and the product, one after the other or one in the
    # other's place; never a third beside them
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2.05 * seq * width * 2


def test_lfm2s_two_operators_compile_with_their_kernels_at_heads_of_64(
        v5e, monkeypatch):
    """LFM2's two operators whole at their cell's step (8 windows of 4,096
    into 2,048, bfloat16), as one chip builds them. The gated short
    convolution: one kernel between its two projections, which reads the
    three ranges of ``(8, 4096, 6144)`` where they lie (no slice of a range,
    no float32 array of a range's size outside it). The attention at 32 query
    heads on 8 key heads of 64: three kernels (the head norm and turn of q
    and of k on a lane tile's two heads; the causal kernel on pairs of key
    heads), the rows one ``while`` under ``mix.attention`` that carries q and
    its result ``(8, 4096, 2048)`` and k and v ``(8, 4096, 512)`` as the
    projections left them; no view a head, no padded copy to heads of 128 and
    no float32 scores in HBM."""
    import re

    import storm_tpu.ops.attention as attention
    import storm_tpu.ops.kda as kda
    import storm_tpu.ops.rope as rope
    from storm_tpu.models import lfm2
    from storm_tpu.ops.platform import dispatch_notes

    for module in (attention, rope, kda):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    x = _spec((8, 4096, 2048), jnp.bfloat16, v5e)

    def served(init):
        return jax.tree.map(lambda a: _spec(a.shape, jnp.bfloat16, v5e),
                            jax.eval_shape(init))

    p = served(lambda: lfm2.conv_mixer_init(jax.random.PRNGKey(0), 2048, 3))
    with dispatch_notes() as seen:
        compiled = jax.jit(lfm2.conv_mixer).lower(p, x).compile()
    assert seen == ["gated_conv=kernel"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and not _loops(text)
    entry = text[text.index("ENTRY"):]  # what is written between fusions
    assert "f32[8,4096,2048]" not in entry and "f32[8,4096,6144]" not in entry
    assert not re.search(r"= \w+\[8,4096,2048\]\S* (slice|copy)\(", entry)
    # the projection's result and the kernel's, nothing else of their size
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 1.05 * 8 * 4096 * (6144 + 2048) * 2

    p = served(lambda: lfm2.attention_mixer_init(
        jax.random.PRNGKey(0), 2048, 32, 8, 64))
    tables = _spec((4096, 32), jnp.float32, v5e)
    with dispatch_notes() as seen:
        compiled = jax.jit(lambda p, x, cos, sin: lfm2.attention_mixer(
            p, x, 32, 8, 64, 1e-5, (cos, sin))).lower(
            p, x, tables, tables).compile()
    assert seen == ["head_norm=kernel", "rotary_turn=lanes",
                    "causal_attention=kernel-grouped-merged-halves"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    (loop,) = _loops(text)
    assert "/mix.attention/while" in loop
    assert loop.count("bf16[8,4096,2048]") >= 2
    assert loop.count("bf16[8,4096,512]") >= 2
    assert not re.search(
        r"\[8,4096,(32|8),(64|128)\]|\[8,(32|8),4096,(64|128)\]", text)
    entry = text[text.index("ENTRY"):]
    assert "f32[8,4096,2048]" not in entry + loop
    assert not re.search(r"f32\[\d+,\d+,4096\]", text)  # scores of a block
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4 * 2 ** 30


def test_ouros_looped_program_compiles_whole_for_one_chip(v5e, monkeypatch):
    """Ouro-2.6B whole at its cell's step (4 windows of 4,096, every
    published width, 48 layers, 4 passes), as one chip builds it: the passes
    are one ``while`` whose body holds the 48 blocks once (48 row loops of the
    causal kernel at one query head a key head: 48 Pallas calls whatever
    ``total_ut_steps`` says, where the parent of PR 74 held 144, a block's
    two turns by the lanes kernel beside its causal kernel, which turns q
    and k itself since), and the compiler's temporaries and arguments are
    what the configuration's ``on_device`` states: a third of the chip in
    parameters, 6.0 GB while a step runs."""
    import json

    import storm_tpu.ops.attention as attention
    import storm_tpu.ops.rope as rope
    from storm_tpu.models.registry import build_model
    from storm_tpu.ops.platform import dispatch_notes

    for module in (attention, rope):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    assert attention.merged_form(16, 16, 4096, 128, 128) == "kernel"
    model = build_model("ouro_2_6b")
    params, state = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, v5e),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    x = _spec((4, 4096), jnp.float32, v5e)
    with dispatch_notes() as seen:
        lowered = jax.jit(model.apply).lower(params, state, x)
    assert seen == ["rotary_turn=causal-kernel",
                    "causal_attention=kernel-merged", "gated_ffn=made-once"]
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 48
    assert text.count(" while(") == 48 + 1
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "ouro_2_6b.json")) as f:
        on_device = json.load(f)["on_device"]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes \
        == on_device["program_temporaries_bucket_4_bytes"]
    assert memory.argument_size_in_bytes \
        == on_device["program_arguments_bucket_4_bytes"]
    assert on_device["parameters_bytes"] == 2 * on_device["parameters"] \
        == 2 * 2_667_974_657
    # under the chip's 16 GB with room, and over the quarter a cell must fill
    total = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    assert 0.25 * 16e9 < on_device["parameters_bytes"] < total < 0.5 * 16e9


@pytest.mark.parametrize("rows,s,heads,kv_heads,dim", [
    (4, 4096, 16, 16, 2048),   # Ouro's cell: a head on its own keys
    (4, 16384, 20, 4, 5120),   # Falcon-H1's: five query heads a key head
], ids=["ouro", "falcon_h1"])
def test_the_rotary_mixer_turns_q_and_k_inside_its_causal_kernel(
        v5e, monkeypatch, rows, s, heads, kv_heads, dim):
    """``models/falcon_h1.py rotary_gqa`` at Ouro's and at Falcon-H1's
    cell's step, as one chip builds it (PR 74): **one** Pallas call a layer
    where there were three (the lanes kernel on q, on k, and the causal
    kernel): the causal kernel takes q and k unturned with the two lane
    tables whole, ``(S, 128)`` float32 in one buffer each, and its scratch
    for the turned k, inside ``_VMEM_LIMIT`` (at 16,384 positions: 16 MB of
    tables beside 16 MB of double-buffered k and v and 4 MB of scratch);
    the rows are one ``while`` under ``mix.attention`` that carries q, k and
    v as the projections left them, and nothing of q's or k's size is
    written between the four products but by them. Handed no ``rotary``, the
    entry compiles to the parent's three calls' worth: the lanes kernel is
    still every other caller's."""
    import re

    import storm_tpu.ops.attention as attention
    import storm_tpu.ops.rope as rope
    from storm_tpu.models.falcon_h1 import rotary_gqa
    from storm_tpu.models.nemotron_h import gqa_mixer_init
    from storm_tpu.ops.platform import dispatch_notes

    for module in (attention, rope):
        monkeypatch.setattr(module, "_use_pallas", lambda: True)
        monkeypatch.setattr(module, "_one_device", lambda: True)
    p = jax.tree.map(
        lambda a: _spec(a.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: gqa_mixer_init(
            jax.random.PRNGKey(0), dim, heads, kv_heads, 128)))
    x = _spec((rows, s, dim), jnp.bfloat16, v5e)
    tables = _spec((s, 64), jnp.float32, v5e)
    with dispatch_notes() as seen:
        compiled = jax.jit(lambda p, x, cos, sin: rotary_gqa(
            p, x, heads, kv_heads, (cos, sin), 128 ** -0.5)).lower(
            p, x, tables, tables).compile()
    grouped = "-grouped" if heads != kv_heads else ""
    assert seen == ["rotary_turn=causal-kernel",
                    f"causal_attention=kernel{grouped}-merged"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    (loop,) = _loops(text)
    assert "/mix.attention/while" in loop
    wide, narrow = (f"bf16[{rows},{s},{h * 128}]" for h in (heads, kv_heads))
    assert loop.count(wide) >= 2 and loop.count(narrow) >= 2
    assert loop.count(f"f32[{s},128]") >= 2  # the lane tables, made once
    # of q's or k's size, between the loop and the parameters: the four
    # products and nothing else
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(
        rf"= bf16\[{rows},{s},\d+\]\S* (?:fusion|copy)\(", entry)) == 4

    q, k = (_spec((rows, s, h * 128), jnp.bfloat16, v5e)
            for h in (heads, kv_heads))
    with dispatch_notes() as seen:
        before = jax.jit(lambda q, k, v, cos, sin: (
            attention.causal_attention_merged(
                *rope.turn_merged((q,), cos, sin, heads),
                *rope.turn_merged((k,), cos, sin, kv_heads), v, heads,
                kv_heads))).lower(q, k, k, tables, tables).compile()
    assert seen == ["rotary_turn=lanes",
                    f"causal_attention=kernel{grouped}-merged"]
    assert before.as_text().count("tpu_custom_call") == 3
