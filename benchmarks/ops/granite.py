"""Operations and least bytes of one Granite 4.0-H step, from shapes: the
whole step (``counts``) and each of its three mechanisms (``kernels``), each
count **of the work the mathematics needs whatever implements it**.

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (which published layers, routed
experts, rows of the tied matrix), the window's length, the rows of a step
and the program's own choices (the scan's chunk, the expert tile).

Operations are two per multiply-add of every matrix product, the router's
among them; the embedding is a gather and costs none; RMSNorm, SiLU,
softplus, softmax, the short convolution, the decays' exponentials and the
top-k run on the vector unit and are left out, as in ``ops/nemotron_h.py``.

- Projections: every token, every held block: a Mamba-2 layer's two, an
  attention layer's four, the router and the shared expert's three.
- ``ssd_scan``: the chunked form **at the published ``mamba_chunk_size``**
  ``Q`` (256), whatever chunk the program runs (a smaller chunk does less
  work within a chunk and is not credited with the larger one's: the share
  says how near the program comes to what the published tiling needs),
  triangles counted half: per token and head ``Q/2 * P`` within the chunk,
  ``Q/2 * N`` a *group* for ``C B^T`` (one group: once for all 128 heads),
  and two ``P * N`` products with the state (to read it and to write it).
- ``gqa_attention``: a query meets ``(S + 1) / 2`` keys, ``2 * head_dim``
  multiply-adds a pair and query head (scores and values).
- ``expert_matmul``: three ``D x F`` products an assignment that falls on a
  held expert. ``counts`` takes the expected number (``top_k * held /
  experts`` a token, which a uniform router gives); ``kernels`` takes the
  number the program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (the scan's ``x``, ``B``, ``C`` and ``y`` in the served type and the
step in float32; attention's ``q`` and result at the query heads' width, its
``k`` and ``v`` at the key heads'; for the experts the held experts' weights
once a layer and an assignment's token in, in the served type, and its
result out in float32).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("layers", list(range(sizes["num_hidden_layers"])))
    held.setdefault("num_local_experts", sizes["num_local_experts"])
    held.setdefault("vocab_size", sizes["vocab_size"])
    return held


def _layers(sizes: dict) -> tuple:
    """``(mamba, attention)``: how many held blocks have each mixer."""
    kinds = [sizes["layer_types"][i] for i in _held(sizes)["layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def _mamba_widths(sizes: dict) -> tuple:
    """``(inner, B and C together)`` channels of a Mamba-2 layer."""
    return (sizes["mamba_n_heads"] * sizes["mamba_d_head"],
            2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"])


def _head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def mamba_projection_parameters(sizes: dict) -> int:
    d = sizes["hidden_size"]
    inner, bc = _mamba_widths(sizes)
    return d * (2 * inner + bc + sizes["mamba_n_heads"]) + inner * d


def mamba_parameters(sizes: dict) -> int:
    inner, bc = _mamba_widths(sizes)
    return (mamba_projection_parameters(sizes)
            + (sizes["mamba_d_conv"] + 1) * (inner + bc)  # taps and bias
            + 3 * sizes["mamba_n_heads"]  # A_log, dt_bias, D
            + inner)  # the gated norm's scale


def attention_parameters(sizes: dict) -> int:
    return 2 * sizes["hidden_size"] * _head_dim(sizes) * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])


def expert_layer_parameters(sizes: dict) -> int:
    """Held experts, the router at its published width, the shared expert."""
    d = sizes["hidden_size"]
    return (_held(sizes)["num_local_experts"] * 3 * d
            * sizes["intermediate_size"] + d * sizes["num_local_experts"]
            + 3 * d * sizes["shared_intermediate_size"])


def parameters(sizes: dict) -> int:
    """Parameters this chip holds: the tied matrix counted once."""
    d = sizes["hidden_size"]
    mamba, attn = _layers(sizes)
    return (mamba * mamba_parameters(sizes)
            + attn * attention_parameters(sizes)
            + (mamba + attn) * (expert_layer_parameters(sizes) + 2 * d)
            + _held(sizes)["vocab_size"] * d + d)


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held blocks that run it. ``assignments``: routed
    assignments that fell on held experts in the step, all blocks together
    (None: the expected number)."""
    held = _held(sizes)
    seq = held["sequence_length"]
    tokens = rows * seq
    mamba, attn = _layers(sizes)
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    q = sizes["mamba_chunk_size"]
    inner, bc = _mamba_widths(sizes)
    scan_macs = heads * (q // 2 * p + 2 * p * n) + groups * (q // 2 * n)
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = _head_dim(sizes)
    if assignments is None:
        assignments = (mamba + attn) * tokens * sizes["num_experts_per_tok"] \
            * held["num_local_experts"] / sizes["num_local_experts"]
    return {
        "ssd_scan": {
            "flops": 2 * mamba * tokens * scan_macs,
            "bytes": mamba * tokens * ((2 * inner + bc) * bytes_per_value
                                       + 4 * heads)},
        "gqa_attention": {
            "flops": attn * rows * hq * 4 * hd * (seq * (seq + 1) // 2),
            "bytes": attn * tokens * 2 * (hq + hkv) * hd * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": (mamba + attn) * held["num_local_experts"] * 3 * d * f
            * bytes_per_value + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window, the expected routing."""
    held = _held(sizes)
    seq = held["sequence_length"]
    mamba, attn = _layers(sizes)
    d = sizes["hidden_size"]
    per_token = 2 * (
        mamba * mamba_projection_parameters(sizes)
        + attn * attention_parameters(sizes)
        + (mamba + attn) * (d * sizes["num_local_experts"]
                            + 3 * d * sizes["shared_intermediate_size"]))
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
