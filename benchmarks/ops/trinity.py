"""Operations and least bytes of one Trinity step, from shapes: the whole
step (``counts``) and each of its three mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (which published layers, the
window's length, the rows of a step, the program's tile). Nothing of a layer
is divided: every head, every routed expert and the whole vocabulary are
held, so only the depth differs from the published model.

Operations are two per multiply-add of every matrix product, the router's
among them; the embeddings are a gather and cost none; RMSNorm (four a
block and two a head), SiLU, sigmoid, softmax, the rotary turn and the top-k
run on the vector unit and are left out, as in ``ops/kimi_linear.py``.

- Projections: every token, every held layer: q, gate and o at the query
  heads' width, k and v at the key heads'; a dense layer's three ``D x F``
  products; an expert layer's router and shared expert.
- Attention: ``2 * head_dim`` multiply-adds a pair of query and key and
  query head. A ``full_attention`` layer's query at ``t`` meets ``t + 1``
  keys, ``S (S + 1) / 2`` pairs a head and window (``full_attention``); a
  ``sliding_attention`` layer's meets ``min(t + 1, W)``, ``W (W + 1) / 2 + (S
  - W) W`` pairs (``window_attention``): the pairs inside the window and no
  other, which is what the program's loop must compute, whatever blocks it
  walks to do so.
- Experts (``expert_matmul``): three ``D x F`` products an assignment.
  Every expert is held, so every assignment is: ``top_k`` a token a layer.
  ``kernels`` takes the number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (attention's ``q`` and result at the query heads' width, its ``k`` and
``v`` at the key heads'; for the experts every expert's weights once a layer
and an assignment's token in, in the served type, and its result out in
float32).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("layers", list(range(sizes["num_hidden_layers"])))
    return held


def _layers(sizes: dict):
    """``(sliding, full, dense, expert)``: how many held layers have a
    window, how many read every key, and how many have a dense feed-forward
    and how many an expert layer."""
    which = _held(sizes)["layers"]
    sliding = sum(1 for i in which
                  if sizes["layer_types"][i] == "sliding_attention")
    dense = sum(1 for i in which if i < sizes["num_dense_layers"])
    return sliding, len(which) - sliding, dense, len(which) - dense


def attention_parameters(sizes: dict) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * hd * (3 * hq + 2 * hkv) + 2 * hd  # q, gate, o; k, v; norms


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    sliding, full, dense, expert = _layers(sizes)
    expert_layer = (d * sizes["num_experts"] + sizes["num_experts"]
                    + (sizes["num_experts"] + sizes["num_shared_experts"])
                    * 3 * d * f)
    return ((sliding + full) * (attention_parameters(sizes) + 4 * d)
            + dense * 3 * d * sizes["intermediate_size"]
            + expert * expert_layer + d + 2 * sizes["vocab_size"] * d)


def pairs(sizes: dict) -> tuple:
    """``(window, full)``: the pairs of query and key a head and window that
    lie inside a sliding layer's window and under a full layer's diagonal."""
    seq = _held(sizes)["sequence_length"]
    w = min(sizes["sliding_window"], seq)
    return w * (w + 1) // 2 + (seq - w) * w, seq * (seq + 1) // 2


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it. ``assignments``: routed
    assignments of the step, all expert layers together (None: ``top_k`` a
    token a layer, which is what a layer that holds every expert is given
    whatever the routing)."""
    held = _held(sizes)
    tokens = rows * held["sequence_length"]
    sliding, full, _, expert = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    in_window, causal = pairs(sizes)
    if assignments is None:
        assignments = expert * tokens * sizes["num_experts_per_tok"]
    moved = tokens * 2 * (hq + hkv) * hd * bytes_per_value
    return {
        "window_attention": {
            "flops": 2 * sliding * rows * hq * 2 * hd * in_window,
            "bytes": sliding * moved},
        "full_attention": {
            "flops": 2 * full * rows * hq * 2 * hd * causal,
            "bytes": full * moved},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": expert * sizes["num_experts"] * 3 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    seq = _held(sizes)["sequence_length"]
    sliding, full, dense, expert = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    per_token = 2 * (
        (sliding + full) * (attention_parameters(sizes)
                            - 2 * sizes["head_dim"])
        + dense * 3 * d * sizes["intermediate_size"]
        + expert * (d * sizes["num_experts"]
                    + sizes["num_shared_experts"] * 3 * d * f))
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
