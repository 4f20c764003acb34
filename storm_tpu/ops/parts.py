"""The names of a model's parts in a device trace, and nothing else.

A model wraps each part in ``jax.named_scope(<one of these>)``; the name
rides the operations' metadata (``op_name``: ``jit(fwd)/.../proj/dot_general``)
into the compiled program and from there into a profiler trace. A reader of
traces imports the same constants, so a model and a reader cannot drift
apart. Flat, one level deep below the layer: a part entered inside another
part (``mix.attention`` under ``mix.elementwise``) is the inner one's.

A scope changes metadata and no value: the compiled code is the same. The
compile cache therefore keys on metadata too (``infer/engine.py
enable_compile_cache``), or a program cached without its names would be
loaded in place of one with them.
"""

EMBED = "embed"  # patch/token embedding
HEAD = "head"  # final norm, classifier or vocabulary product, softmax
NORM = "norm"  # a block's pre-norms and residual adds
PROJ = "proj"  # every dense product with weights that is not an expert's
FFN = "ffn"  # a block's own dense feed-forward, its products and its gate,
# where a plan names it apart (models/falcon_h1.py: what is left under
# ``proj`` is then the mixers' projections alone; the plans before it keep
# their feed-forward under ``proj``)
MIX_ELEMENTWISE = "mix.elementwise"  # conv, gates, norms, re-tiling, layout
MIX_GATED_CONV = "mix.gated_conv"  # ops/kda.py gated_conv: a gated short
# convolution's product, taps and second gate, where they are the mixer
# (models/lfm2.py); its two projections stay ``proj``
MIX_KDA_TABLES = "mix.kda_tables"  # ops/kda.py: the within-chunk tables
MIX_KDA_SCAN = "mix.kda_scan"  # ops/kda.py: the loop over chunks
MIX_SSD_SCAN = "mix.ssd_scan"  # ops/ssd.py: the loop over chunks
MIX_ATTENTION = "mix.attention"  # causal_attention's loop, ViT's attention
MIX_WINDOW_ATTENTION = "mix.window_attention"  # ... its loop where a query
# reads its last ``window`` keys alone: the same shapes, so a name apart
MIX_ROPE = "mix.rope"  # ops/rope.py: the position tables and the turn
MIX_SPARSE_SELECT = "mix.sparse_select"  # ops/sparse_attention.py: pooled
# keys, the first pass over them, the blocks' scores, the top-k, the counts
MIX_SPARSE_ATTENTION = "mix.sparse_attention"  # ... its second pass
MIX_INDEX_SELECT = "mix.index_select"  # ... its other first pass: a learned
# indexer's scores of every causal pair, each query's top keys, the mask,
# the count of blocks picked
MIX_EVA_CHUNKS = "mix.eva_chunks"  # ops/eva_attention.py: the two poolings
# of every chunk's keys and values into one summary each
MIX_EVA_ATTENTION = "mix.eva_attention"  # ... the loop over rows: a window's
# keys read exactly and every earlier summary, under one softmax
MOE_ROUTE = "moe.route"  # router, top-k, the dispatch (one sort with its
# payloads, a bisection for the counts, rows by comparison), zeroed buffer
MOE_EXPERTS = "moe.experts"  # topk_moe_layer's loop over tiles
MOE_COMBINE = "moe.combine"  # _combine_held: sort, the loops, last pass

VOCABULARY = (EMBED, HEAD, NORM, PROJ, MIX_ELEMENTWISE, MIX_KDA_TABLES,
              MIX_KDA_SCAN, MIX_SSD_SCAN, MIX_ATTENTION, MIX_ROPE, MOE_ROUTE,
              MOE_EXPERTS, MOE_COMBINE, MIX_SPARSE_SELECT,
              MIX_SPARSE_ATTENTION, MIX_EVA_CHUNKS, MIX_EVA_ATTENTION,
              MIX_WINDOW_ATTENTION, MIX_INDEX_SELECT, FFN, MIX_GATED_CONV)


def part_of(op_name: str):
    """The part an operation's ``op_name`` path lies under: the innermost
    component that is of the vocabulary, None where there is none."""
    for piece in reversed(op_name.split("/")):
        if piece in _KNOWN:
            return piece
    return None


_KNOWN = frozenset(VOCABULARY)
