"""Operations and least bytes of one Solar Open 2 step, from shapes: the whole
step (``counts``) and each of its three mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers, routed experts, rows of
the vocabulary), the window's length, the rows of a step and the program's two
own choices (the KDA chunk, the expert tile).

Operations are two per multiply-add of every matrix product, the router's
among them; the embeddings are a gather and cost none; RMSNorm, SiLU,
sigmoid, softmax, the short convolution, the decay's exponentials and the
top-k run on the vector unit and are left out, as in ``ops/kimi_linear.py``.

- Projections: every token, every held layer: a KDA layer's q, k, v, o, its
  two low-rank pairs and its step; a softmax layer's q, k, v, gate and o; the
  router and the shared expert.
- KDA's state (``kda_scan``): the chunked form at the program's chunk ``C``,
  triangles counted half, as ``ops/kimi_linear.py`` counts it: per token and
  head ``C/2 * dk`` each for the two within-chunk tables, ``C/2 * (dk + dv)``
  to apply the solved triangle, three ``dk * dv`` products with the state and
  ``C/2 * dv`` within the chunk.
- Causal attention (``gqa_attention``): a query meets ``(S + 1) / 2`` keys,
  ``2 * head_dim`` multiply-adds a pair and query head.
- Experts (``expert_matmul``): three ``D x F`` products an assignment that
  falls on a held expert. ``counts`` takes the expected number (``top_k *
  held / experts`` a token, which a uniform router gives); ``kernels`` takes
  the number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (KDA's q, k, v and result in the served type, the decay and the step in
float32; attention's ``q`` and result at the query heads' width, its ``k``
and ``v`` at the key heads'; for the experts the held experts' weights once
a layer).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("num_hidden_layers", sizes["num_hidden_layers"])
    held.setdefault("n_routed_experts", sizes["n_routed_experts"])
    held.setdefault("vocab_size", sizes["vocab_size"])
    held.setdefault("kda_chunk", 64)
    return held


def _layers(sizes: dict):
    """``(gqa, kda)``: how many held layers are of each kind; every one of
    them has an expert layer."""
    held = _held(sizes)["num_hidden_layers"]
    gqa = sum(1 for i in sizes["gqa_layers"] if i < held)
    return gqa, held - gqa


def _kda_width(sizes: dict) -> int:
    la = sizes["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def kda_projection_parameters(sizes: dict) -> int:
    d, la = sizes["hidden_size"], sizes["linear_attn_config"]
    w, r, h = _kda_width(sizes), la["head_dim"], la["num_heads"]
    return (4 * d * w                  # q, k, v, o
            + 2 * (d * r + r * w)      # decay and output gate, low rank
            + d * h)                   # the step


def gqa_projection_parameters(sizes: dict) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * hd * ((3 if sizes["use_gqa_gate"] else 2) * hq + 2 * hkv)


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    la = sizes["linear_attn_config"]
    held = _held(sizes)
    gqa, kda = _layers(sizes)
    w = _kda_width(sizes)
    kda_mixer = (kda_projection_parameters(sizes)
                 + 3 * la["short_conv_kernel_size"] * w   # convolutions
                 + la["num_heads"] + w + la["head_dim"])  # A_log, dt_bias, norm
    expert = 3 * d * f
    expert_layer = (d * sizes["n_routed_experts"] + sizes["n_routed_experts"]
                    + (held["n_routed_experts"] + sizes["n_shared_experts"])
                    * expert)
    norms = 2 * held["num_hidden_layers"] * d + d
    return (kda * kda_mixer + gqa * gqa_projection_parameters(sizes)
            + (gqa + kda) * expert_layer + norms + 2 * held["vocab_size"] * d)


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it. ``assignments``: routed
    assignments that fell on held experts in the step, all expert layers
    together (None: the expected number)."""
    held = _held(sizes)
    la = sizes["linear_attn_config"]
    seq = held["sequence_length"]
    tokens = rows * seq
    gqa, kda = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    heads, dk = la["num_heads"], la["head_dim"]
    dv, c = dk, held["kda_chunk"]
    w = heads * dk
    scan_macs = heads * (c // 2 * dk * 2 + c // 2 * (dk + dv)
                         + 3 * dk * dv + c // 2 * dv)
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    if assignments is None:
        assignments = (gqa + kda) * tokens * sizes["num_experts_per_tok"] \
            * held["n_routed_experts"] / sizes["n_routed_experts"]
    return {
        "kda_scan": {
            "flops": 2 * kda * tokens * scan_macs,
            "bytes": kda * tokens * (4 * w * bytes_per_value + 4 * w
                                     + 4 * heads)},
        "gqa_attention": {
            "flops": 2 * gqa * tokens * hq * 2 * hd * (seq + 1) / 2,
            "bytes": gqa * tokens * 2 * (hq + hkv) * hd * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": (gqa + kda) * held["n_routed_experts"] * 3 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window, the expected routing."""
    held = _held(sizes)
    seq = held["sequence_length"]
    gqa, kda = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    per_token = 2 * (
        kda * kda_projection_parameters(sizes)
        + gqa * gqa_projection_parameters(sizes)
        + (gqa + kda) * (d * sizes["n_routed_experts"]
                         + sizes["n_shared_experts"] * 3 * d * f))
    parts = kernels(sizes, 1, 2)
    return (seq * per_token + sum(k["flops"] for k in parts.values())
            + 2 * d * held["vocab_size"])  # the head, at the last position


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
    held = _held(sizes)
    return {
        "flops": rows * flops_per_row(sizes),
        "bytes": steps * parameters(sizes) * bytes_per_value
        + rows * 4 * (held["sequence_length"] + held["vocab_size"]),
    }
