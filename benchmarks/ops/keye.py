"""Operations and least bytes of one step of Keye-VL-2.0's language model,
from shapes: the whole step (``counts``) and each of its three mechanisms
(``kernels``), each count **of the work the mathematics needs whatever
implements it**.

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (which published layers, the
window's length, the rows of a step, the program's tiles). Nothing of a layer
is divided: every head, the indexer, every expert and the whole vocabulary
are held, so only the depth differs from the published model.

Operations are two per multiply-add of every matrix product, the router's
and the indexer's among them; the embeddings are a gather and cost none;
RMSNorm, LayerNorm, SiLU, ReLU and the indexer's weighted sum, softmax, the
rotary turn and both top-k run on the vector unit and are left out, as in
``ops/kimi_linear.py``.

- Projections: every token, every held layer: q and o at the query heads'
  width, k and v at the key heads', the indexer's three (its queries, its
  one key, its weights), the router.
- ``index_select``: ``2 * indexer_head_dim`` operations a scored pair and
  indexer head. A query at ``t >= topk`` scores its ``t + 1`` keys; one
  before reads every key and needs no score: ``S (S + 1) / 2 - K (K + 1) /
  2`` pairs a window and layer (``K = topk``), with the indexer's queries,
  key and weights read once and nothing written (the selection is what the
  second pass reads, however it is handed over).
- ``sparse_attention``: ``2 * head_dim`` multiply-adds a pair of query and
  picked key and query head, scores and values: ``K (K + 1) / 2 + (S - K) K``
  pairs a head and window, whatever blocks a kernel walks to reach them;
  q, k, v in and the result out once.
- ``expert_matmul``: three ``D x F`` products an assignment. Every expert is
  held, so every assignment is: ``top_k`` a token a layer. ``kernels`` takes
  the number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (for the experts every expert's weights once a layer and an
assignment's token in, in the served type, and its result out in float32).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("layers", list(range(sizes["num_hidden_layers"])))
    return held


def attention_parameters(sizes: dict) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * hd * (2 * hq + 2 * hkv) + 2 * hd  # q, o; k, v; head norms


def indexer_parameters(sizes: dict) -> int:
    ix = sizes["sa_config"]
    ih, idim = ix["indexer_num_heads"], ix["indexer_head_dim"]
    # queries, the one key, the weights; the key's norm (scale and bias)
    return sizes["hidden_size"] * (ih * idim + idim + ih) + 2 * idim


def expert_parameters(sizes: dict) -> int:
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return sizes["num_experts"] * 3 * d * f + d * sizes["num_experts"]


def block_parameters(sizes: dict) -> int:
    return (attention_parameters(sizes) + indexer_parameters(sizes)
            + expert_parameters(sizes) + 2 * sizes["hidden_size"])


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d = sizes["hidden_size"]
    return (len(_held(sizes)["layers"]) * block_parameters(sizes)
            + d + 2 * sizes["vocab_size"] * d)


def pairs(sizes: dict) -> tuple:
    """``(scored, picked)`` pairs of query and key a window and layer: those
    the indexer must score (a head of it), and those a query head reads."""
    seq = _held(sizes)["sequence_length"]
    k = min(sizes["sa_config"]["topk"], seq)
    causal = seq * (seq + 1) // 2
    inside = k * (k + 1) // 2
    return causal - inside, inside + (seq - k) * k


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers. ``assignments``: routed assignments
    of the step, all layers together (None: ``top_k`` a token a layer, which
    is what a layer that holds every expert is given whatever the
    routing)."""
    held = _held(sizes)
    layers = len(held["layers"])
    tokens = rows * held["sequence_length"]
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    ix = sizes["sa_config"]
    ih, idim = ix["indexer_num_heads"], ix["indexer_head_dim"]
    scored, picked = pairs(sizes)
    if assignments is None:
        assignments = layers * tokens * sizes["num_experts_per_tok"]
    return {
        "index_select": {
            "flops": layers * rows * 2 * ih * idim * scored,
            "bytes": layers * tokens * (ih * idim + idim + ih)
            * bytes_per_value},
        "sparse_attention": {
            "flops": 2 * layers * rows * hq * 2 * hd * picked,
            "bytes": layers * tokens * 2 * (hq + hkv) * hd
            * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": layers * sizes["num_experts"] * 3 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    seq = _held(sizes)["sequence_length"]
    layers = len(_held(sizes)["layers"])
    d = sizes["hidden_size"]
    ix = sizes["sa_config"]
    per_token = 2 * layers * (
        attention_parameters(sizes) - 2 * sizes["head_dim"]
        + indexer_parameters(sizes) - 2 * ix["indexer_head_dim"]
        + d * sizes["num_experts"])
    parts = kernels(sizes, 1, 2)
    return (seq * per_token + sum(k["flops"] for k in parts.values())
            + 2 * d * sizes["vocab_size"])  # the head, at the last position


def rows_per_step(op_names: list, sizes: dict):
    """The windows a compiled program was built for, read off the shapes in
    its operations' names: the commonest ``B`` among ``[B,<window>,<hidden>]``.
    None where no operation names such a shape."""
    seq = _held(sizes)["sequence_length"]
    found = re.findall(rf"\[(\d+),{seq},{sizes['hidden_size']}\]",
                       " ".join(op_names))
    if not found:
        return None
    return int(max(set(found), key=found.count))


def counts(sizes: dict, rows: int, steps: int, bytes_per_value: int) -> dict:
    """``rows`` windows served in ``steps`` executions of the program."""
    return {
        "flops": rows * flops_per_row(sizes),
        "bytes": steps * parameters(sizes) * bytes_per_value
        + rows * 4 * (_held(sizes)["sequence_length"] + sizes["vocab_size"]),
    }
