"""Operations and least bytes of one Kimi K2 step, from shapes: the whole step
(``counts``) and its two loops (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers, routed experts, rows of
the vocabulary), the window's length, the rows of a step and the program's
expert tile.

Operations are two per multiply-add of every matrix product, the router's
among them; the embeddings are a gather and cost none; RMSNorm, SiLU,
sigmoid, softmax, the rotary turn and the top-k run on the vector unit and
are left out, as in ``ops/vit.py`` and ``ops/kimi_linear.py``.

- Projections: every token, every held layer: the low-rank queries (``q_a``,
  ``q_b``), the latent and the shared key (``kv_a``), its expansion
  (``kv_b``), ``o``; the dense feed-forward or the shared expert.
- Causal latent attention (``mla_rope_attention``): a query meets ``(S + 1) /
  2`` keys, ``qk + v`` multiply-adds a pair and head.
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
    held.setdefault("n_routed_experts", sizes["n_routed_experts"])
    held.setdefault("vocab_size", sizes["vocab_size"])
    return held


def _layers(sizes: dict):
    """``(dense, expert)``: how many held layers are of each kind."""
    held = _held(sizes)["num_hidden_layers"]
    dense = min(held, sizes["first_k_dense_replace"])
    return dense, held - dense


def mla_projection_parameters(sizes: dict) -> int:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v, rank, q_rank = (sizes["v_head_dim"], sizes["kv_lora_rank"],
                       sizes["q_lora_rank"])
    return (d * q_rank + q_rank * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    held = _held(sizes)
    dense, moe = _layers(sizes)
    mixer = (mla_projection_parameters(sizes) + sizes["q_lora_rank"]
             + sizes["kv_lora_rank"])  # and the two norms inside it
    expert = 3 * d * f
    expert_layer = (d * sizes["n_routed_experts"] + sizes["n_routed_experts"]
                    + (held["n_routed_experts"] + sizes["n_shared_experts"])
                    * expert)
    norms = 2 * held["num_hidden_layers"] * d + d
    return ((dense + moe) * mixer + moe * expert_layer
            + dense * 3 * d * sizes["intermediate_size"] + norms
            + 2 * held["vocab_size"] * d)


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it. ``assignments``: routed
    assignments that fell on held experts in the step, all expert layers
    together (None: the expected number)."""
    held = _held(sizes)
    seq = held["sequence_length"]
    tokens = rows * seq
    dense, moe = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    h = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    v = sizes["v_head_dim"]
    if assignments is None:
        assignments = moe * tokens * sizes["num_experts_per_tok"] \
            * held["n_routed_experts"] / sizes["n_routed_experts"]
    return {
        "mla_rope_attention": {
            "flops": 2 * (dense + moe) * tokens * h * (qk + v) * (seq + 1) / 2,
            # q, k and v in, o out, in the served type
            "bytes": (dense + moe) * tokens * h * (2 * qk + 2 * v)
            * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": moe * held["n_routed_experts"] * 3 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window, the expected routing."""
    held = _held(sizes)
    seq = held["sequence_length"]
    dense, moe = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    per_token = 2 * (
        (dense + moe) * mla_projection_parameters(sizes)
        + dense * 3 * d * sizes["intermediate_size"]
        + moe * (d * sizes["n_routed_experts"]
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
