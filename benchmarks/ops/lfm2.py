"""Operations and least bytes of one LFM2 step, from shapes: the whole step
(``counts``) and each of its three mechanisms (``kernels``), each count **of
the work the mathematics needs whatever implements it**.

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (which published layers, the
head's width the config leaves unsaid), the window's length, the rows of a
step and the program's own choices (the expert tile).

Operations are two per multiply-add of every matrix product, the router's
among them; the embedding is a gather and costs none; RMSNorm, SiLU, the
sigmoid, softmax, the rotary turn and the top-k run on the vector unit and
are left out of the whole step's count, as in ``ops/granite.py``.

- Projections: every token, every held layer: a convolution operator's two
  (``hidden x 3 hidden`` and ``hidden x hidden``), an attention operator's
  four, a dense layer's three, an expert layer's router.
- ``gated_conv``: per token, layer and channel one product before the taps,
  ``conv_L_cache`` products and one add fewer for them, one product after:
  ``2 + (2 conv_L_cache - 1)`` operations (7 at three taps), all on the
  vector unit, so the kernel is **bound by its bytes**: the three ranges in
  and the result out, ``4 x hidden`` values a token and layer.
- ``attention``: a query meets ``(S + 1) / 2`` keys, ``2 * head_dim``
  multiply-adds a pair and query head (scores and values).
- ``expert_matmul``: three ``D x F`` products an assignment. Every routed
  expert is held, so a step's assignments are ``tokens * experts a token``
  an expert layer whatever the routing; ``kernels`` takes the number the
  program counted where the caller has it.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (attention's ``q`` and result at the query heads' width, its ``k`` and
``v`` at the key heads'; for the experts the held weights once a layer and an
assignment's token in, in the served type, and its result out in float32).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("layers", list(range(sizes["num_hidden_layers"])))
    held.setdefault("head_dim",
                    sizes["hidden_size"] // sizes["num_attention_heads"])
    return held


def _layers(sizes: dict) -> tuple:
    """``(conv dense, attention dense, conv expert, attention expert)``: how
    many held layers have each operator under each feed-forward."""
    found = [0, 0, 0, 0]
    for i in _held(sizes)["layers"]:
        found[2 * (i >= sizes["num_dense_layers"])
              + (sizes["layer_types"][i] == "full_attention")] += 1
    return tuple(found)


def conv_parameters(sizes: dict) -> int:
    """``W_in``, the taps, ``W_out``."""
    d = sizes["hidden_size"]
    return d * 3 * d + sizes["conv_L_cache"] * d + d * d


def attention_parameters(sizes: dict) -> int:
    """``W_q``, ``W_o``, ``W_k``, ``W_v`` and the two head norms."""
    hd = _held(sizes)["head_dim"]
    return 2 * sizes["hidden_size"] * hd * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"]) + 2 * hd


def dense_parameters(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_layer_parameters(sizes: dict) -> int:
    """Every routed expert, the router and its selection bias."""
    d, e = sizes["hidden_size"], sizes["num_experts"]
    return e * 3 * d * sizes["moe_intermediate_size"] + d * e + e


def parameters(sizes: dict) -> int:
    """Parameters this chip holds: the tied matrix counted once."""
    d = sizes["hidden_size"]
    cd, ad, ce, ae = _layers(sizes)
    return ((cd + ce) * conv_parameters(sizes)
            + (ad + ae) * attention_parameters(sizes)
            + (cd + ad) * dense_parameters(sizes)
            + (ce + ae) * expert_layer_parameters(sizes)
            + sum((cd, ad, ce, ae)) * 2 * d
            + sizes["vocab_size"] * d + d)


def kernels(sizes: dict, rows: int, bytes_per_value: int,
            assignments=None) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it. ``assignments``: routed
    assignments in the step, all expert layers together (None: ``tokens *
    experts a token`` a layer, which every routing gives where every expert
    is held)."""
    held = _held(sizes)
    seq = held["sequence_length"]
    tokens = rows * seq
    cd, ad, ce, ae = _layers(sizes)
    conv, attn, moe = cd + ce, ad + ae, ce + ae
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = held["head_dim"]
    if assignments is None:
        assignments = moe * tokens * sizes["num_experts_per_tok"]
    return {
        "gated_conv": {
            "flops": conv * tokens * d * (2 + 2 * sizes["conv_L_cache"] - 1),
            "bytes": conv * tokens * 4 * d * bytes_per_value},
        "attention": {
            "flops": attn * rows * hq * 4 * hd * (seq * (seq + 1) // 2),
            "bytes": attn * tokens * 2 * (hq + hkv) * hd * bytes_per_value},
        "expert_matmul": {
            "flops": 2 * assignments * 3 * d * f,
            "bytes": moe * sizes["num_experts"] * 3 * d * f * bytes_per_value
            + assignments * d * (bytes_per_value + 4)},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    seq = _held(sizes)["sequence_length"]
    cd, ad, ce, ae = _layers(sizes)
    d = sizes["hidden_size"]
    per_token = 2 * (
        (cd + ce) * (conv_parameters(sizes) - sizes["conv_L_cache"] * d)
        + (ad + ae) * (attention_parameters(sizes)
                       - 2 * _held(sizes)["head_dim"])
        + (cd + ad) * dense_parameters(sizes)
        + (ce + ae) * d * sizes["num_experts"])
    parts = kernels(sizes, 1, 2)
    return (seq * per_token + parts["attention"]["flops"]
            + parts["expert_matmul"]["flops"]
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
