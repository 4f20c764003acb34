"""Operations and least bytes of one Nemotron-H step, from shapes: the whole
step (``counts``) and each of its three mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers and their letters of the
pattern, routed experts, rows of the vocabulary), the window's length and the
program's two own choices (the scan's chunk, the expert tile).

Operations are two per multiply-add of every matrix product: the embeddings
are a gather and cost none; RMSNorm, SiLU, softplus, sigmoid, softmax, the
squared ReLU, the short convolution, the decays' exponentials and the top-k
run on the vector unit and are left out, as in ``ops/vit.py``.

- Projections: every token, every held layer (a Mamba-2 layer's two, an
  attention layer's four, an expert layer's router and shared expert).
- The Mamba-2 state (``ssd_scan``): the chunked form at the program's chunk
  ``Q``, triangles counted half: per token and head ``Q/2 * P`` within the
  chunk, ``Q/2 * N`` a *group* for ``C B^T`` (shared by the group's heads),
  and two ``P * N`` products with the state (to read it and to write it).
  (Token by token the recurrence needs the same two ``P * N`` products, on
  the vector unit.)
- Causal attention (``gqa_attention``): a query meets ``(S + 1) / 2`` keys,
  ``2 * head_dim`` multiply-adds a pair and query head.
- Experts (``expert_matmul``): two ``D x F`` products an assignment that
  falls on a held expert. ``counts`` takes the expected number (``top_k *
  held / experts`` a token, which a uniform router gives); ``kernels`` takes
  the number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (the scan's ``x``, ``B``, ``C`` and ``y`` in the served type and the
step in float32; attention's ``q`` and result at the query heads' width, its
``k`` and ``v`` at the key heads'; for the experts the held experts' weights
once a layer).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("num_hidden_layers", sizes["num_hidden_layers"])
    held.setdefault("n_routed_experts", sizes["n_routed_experts"])
    held.setdefault("vocab_size", sizes["vocab_size"])
    held.setdefault("ssd_chunk", sizes["chunk_size"])
    return held


def _layers(sizes: dict):
    """``(mamba, experts, attention)``: how many held layers are of each
    kind, by the first letters of the published pattern."""
    letters = sizes["hybrid_override_pattern"][
        :_held(sizes)["num_hidden_layers"]]
    return letters.count("M"), letters.count("E"), letters.count("*")


def _mamba_widths(sizes: dict):
    """``(inner, B and C together)`` channels of a Mamba-2 layer."""
    return (sizes["mamba_num_heads"] * sizes["mamba_head_dim"],
            2 * sizes["n_groups"] * sizes["ssm_state_size"])


def mamba_projection_parameters(sizes: dict) -> int:
    d = sizes["hidden_size"]
    inner, bc = _mamba_widths(sizes)
    return d * (2 * inner + bc + sizes["mamba_num_heads"]) + inner * d


def attention_projection_parameters(sizes: dict) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    return 2 * d * hd * (sizes["num_attention_heads"]
                         + sizes["num_key_value_heads"])


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = _held(sizes)
    mamba, moe, attn = _layers(sizes)
    inner, bc = _mamba_widths(sizes)
    mamba_layer = (mamba_projection_parameters(sizes)
                   + (sizes["conv_kernel"] + 1) * (inner + bc)  # taps, bias
                   + 3 * sizes["mamba_num_heads"]  # A_log, D, dt_bias
                   + inner + d)                    # the two norms
    expert_layer = (d * sizes["n_routed_experts"] + sizes["n_routed_experts"]
                    + held["n_routed_experts"] * 2 * d * f
                    + sizes["n_shared_experts"] * 2 * d
                    * sizes["moe_shared_expert_intermediate_size"] + d)
    attn_layer = attention_projection_parameters(sizes) + d
    return (mamba * mamba_layer + moe * expert_layer + attn * attn_layer
            + d + 2 * held["vocab_size"] * d)


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it. ``assignments``: routed
    assignments that fell on held experts in the step, all expert layers
    together (None: the expected number)."""
    held = _held(sizes)
    seq = held["sequence_length"]
    tokens = rows * seq
    mamba, moe, attn = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n = sizes["n_groups"], sizes["ssm_state_size"]
    q = held["ssd_chunk"]
    inner, bc = _mamba_widths(sizes)
    scan_macs = heads * (q // 2 * p + 2 * p * n) + groups * (q // 2 * n)
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    if assignments is None:
        assignments = moe * tokens * sizes["num_experts_per_tok"] \
            * held["n_routed_experts"] / sizes["n_routed_experts"]
    return {
        "ssd_scan": {
            "flops": 2 * mamba * tokens * scan_macs,
            "bytes": mamba * tokens * ((2 * inner + bc) * bytes_per_value
                                       + 4 * heads)},
        "gqa_attention": {
            "flops": 2 * attn * tokens * hq * 2 * hd * (seq + 1) / 2,
            "bytes": attn * tokens * 2 * (hq + hkv) * hd * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 2 * d * f,
            "bytes": moe * held["n_routed_experts"] * 2 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window, the expected routing."""
    held = _held(sizes)
    seq = held["sequence_length"]
    mamba, moe, attn = _layers(sizes)
    d = sizes["hidden_size"]
    per_token = 2 * (
        mamba * mamba_projection_parameters(sizes)
        + attn * attention_projection_parameters(sizes)
        + moe * (d * sizes["n_routed_experts"] + sizes["n_shared_experts"]
                 * 2 * d * sizes["moe_shared_expert_intermediate_size"]))
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
