"""Operations and least bytes of one Kimi-Linear step, from shapes: the whole
step (``counts``) and each of its three mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers, routed experts, rows of
the vocabulary), the window's length and the program's two own choices (the
KDA chunk, the expert tile).

Operations are two per multiply-add of every matrix product: the embeddings
are a gather and cost none; RMSNorm, SiLU, sigmoid, softmax, the short
convolution, the decay's exponentials and the top-k run on the vector unit
and are left out, as in ``ops/vit.py``.

- Projections: every token, every held layer.
- KDA's state (``kda_scan``): the chunked form at the program's chunk ``C``,
  triangles counted half: per token and head ``C/2 * dk`` each for the two
  within-chunk tables, ``C/2 * (dk + dv)`` to apply the solved triangle,
  three ``dk * dv`` products with the state and ``C/2 * dv`` within the
  chunk. (Token by token the recurrence needs three ``dk * dv`` products,
  all on the vector unit; the chunked count is within a third of it.)
- Causal attention (``mla_attention``): a query meets ``(S + 1) / 2`` keys,
  ``dk + dv`` multiply-adds a pair and head.
- Experts (``expert_matmul``): three ``D x F`` products an assignment that
  falls on a held expert. ``counts`` takes the expected number (``top_k *
  held / experts`` a token, which a uniform router gives); ``kernels`` takes
  the number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (and for the experts the held experts' weights once a layer).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("num_hidden_layers", sizes["num_hidden_layers"])
    held.setdefault("num_experts", sizes["num_experts"])
    held.setdefault("vocab_size", sizes["vocab_size"])
    held.setdefault("kda_chunk", 64)
    return held


def _layers(sizes: dict):
    """``(kda, mla, expert)``: how many held layers are of each kind."""
    held = _held(sizes)["num_hidden_layers"]
    la = sizes["linear_attn_config"]
    return (sum(1 for i in la["kda_layers"] if i <= held),
            sum(1 for i in la["full_attn_layers"] if i <= held),
            max(0, held - sizes["first_k_dense_replace"]))


def _kda_width(sizes):
    la = sizes["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def kda_projection_parameters(sizes: dict) -> int:
    d, la = sizes["hidden_size"], sizes["linear_attn_config"]
    w, r, h = _kda_width(sizes), la["head_dim"], la["num_heads"]
    return (4 * d * w                  # q, k, v, o
            + 2 * (d * r + r * w)      # decay and output gate, low rank
            + d * h)                   # beta


def mla_projection_parameters(sizes: dict) -> int:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    la = sizes["linear_attn_config"]
    held = _held(sizes)
    kda, mla, moe = _layers(sizes)
    w = _kda_width(sizes)
    kda_mixer = (kda_projection_parameters(sizes)
                 + 3 * la["short_conv_kernel_size"] * w   # convolutions
                 + la["num_heads"] + w + la["head_dim"])  # A_log, dt_bias, norm
    mla_mixer = mla_projection_parameters(sizes) + sizes["kv_lora_rank"]
    expert = 3 * d * f
    expert_layer = (d * sizes["num_experts"] + sizes["num_experts"]
                    + (held["num_experts"] + sizes["num_shared_experts"])
                    * expert)
    dense = sizes["first_k_dense_replace"] * 3 * d * sizes["intermediate_size"]
    norms = 2 * held["num_hidden_layers"] * d + d
    return (kda * kda_mixer + mla * mla_mixer + moe * expert_layer + dense
            + norms + 2 * held["vocab_size"] * d)


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
    kda, mla, moe = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    heads, dk = la["num_heads"], la["head_dim"]
    dv, c = dk, held["kda_chunk"]
    w = heads * dk
    scan_macs = heads * (c // 2 * dk * 2 + c // 2 * (dk + dv)
                         + 3 * dk * dv + c // 2 * dv)
    h = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    v = sizes["v_head_dim"]
    if assignments is None:
        assignments = moe * tokens * sizes["num_experts_per_token"] \
            * held["num_experts"] / sizes["num_experts"]
    return {
        "kda_scan": {
            "flops": 2 * kda * tokens * scan_macs,
            # q, k, v in and o out in the served type, the decay in float32
            "bytes": kda * tokens * (4 * w * bytes_per_value + 4 * w
                                     + 4 * heads)},
        "mla_attention": {
            "flops": 2 * mla * tokens * h * (qk + v) * (seq + 1) / 2,
            "bytes": mla * tokens * h * (2 * qk + 2 * v) * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": moe * held["num_experts"] * 3 * d * f * bytes_per_value
            + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window, the expected routing."""
    held = _held(sizes)
    seq = held["sequence_length"]
    kda, mla, moe = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    per_token = 2 * (
        kda * kda_projection_parameters(sizes)
        + mla * mla_projection_parameters(sizes)
        + sizes["first_k_dense_replace"] * 3 * d * sizes["intermediate_size"]
        + moe * (d * sizes["num_experts"]
                 + sizes["num_shared_experts"] * 3 * d * f))
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
