"""Operations and least bytes of one MiniCPM-SALA step, from shapes: the whole
step (``counts``) and its three mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers and their
``mixer_types``), the window's length, the rows of a step, the scan's chunk
and the sparse attention's assumed sizes (``held.sparse``).

Operations are two per multiply-add of every matrix product; the embeddings
are a gather and cost none; RMSNorm, SiLU, sigmoid, softmax, the rotary turn,
the decays, the max-pool and the top-k run on the vector unit and are left
out, as in ``ops/vit.py`` and ``ops/nemotron_h.py``. **The work is the
model's, whatever implements it.**

- Projections: every token, every held layer: a ``minicpm4`` mixer's ``q``,
  ``k``, ``v``, gate and ``o``, a ``lightning-attn`` mixer's five square
  products, the SwiGLU's three.
- The selection (``sparse_select``): every query head against the pooled keys
  its position sees, ``head_dim`` multiply-adds a pair; none in a window of
  ``dense_len`` or less.
- The attention over the picked blocks (``sparse_attention``): a query of each
  head meets the keys its ``topk`` blocks hold up to its position (all the
  causal keys while there are no more blocks than that, and in a window of
  ``dense_len`` or less), ``2 * head_dim`` multiply-adds a pair.
- The lightning state (``lightning_scan``): the chunked form at the program's
  chunk ``Q``, triangles counted half, as ``ops/nemotron_h.py`` counts
  Mamba-2's: per token and head ``Q/2 * d`` within the chunk, ``Q/2 * d`` for
  ``q k^T`` (a head's own here: a group a head), and two ``d * d`` products
  with the state (to read it and to write it).

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once in the served type: the selection's ``q`` and ``k``; the attention's
``q`` and result at the query heads' width and **the window's keys and
values once**, whichever blocks are picked; the scan's ``q``, ``k``, ``v``
and result.
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("num_hidden_layers", sizes["num_hidden_layers"])
    held.setdefault("mixer_types",
                    sizes["mixer_types"][:held["num_hidden_layers"]])
    return held


def _layers(sizes: dict):
    """``(minicpm4, lightning)``: how many held layers are of each kind."""
    kinds = _held(sizes)["mixer_types"]
    return kinds.count("minicpm4"), kinds.count("lightning-attn")


def mixer_projection_parameters(sizes: dict) -> tuple:
    """``(minicpm4, lightning)``: a mixer's parameters in matrices."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    inner = sizes["lightning_nh"] * sizes["lightning_head_dim"]
    return (3 * d * hq * hd + 2 * d * hkv * hd, 5 * d * inner)


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    sparse, lightning = _layers(sizes)
    p_sparse, p_lightning = mixer_projection_parameters(sizes)
    inner = sizes["lightning_nh"] * sizes["lightning_head_dim"]
    norms = 2 * (sparse + lightning) * d + d
    return (sparse * (p_sparse + 2 * sizes["head_dim"])
            + lightning * (p_lightning + 2 * sizes["lightning_head_dim"]
                           + inner)
            + (sparse + lightning) * 3 * d * f + norms
            + 2 * sizes["vocab_size"] * d)


def keys_read(sizes: dict) -> int:
    """The keys one group's queries read in one window, summed over its
    positions."""
    sparse = _held(sizes)["sparse"]
    seq, block = _held(sizes)["sequence_length"], sparse["block_size"]
    if seq <= sparse["dense_len"]:
        return seq * (seq + 1) // 2
    # the query's own block up to its position, and whole blocks before it
    return sum(t % block + 1 + block * min(t // block, sparse["topk"] - 1)
               for t in range(seq))


def kernels(sizes: dict, rows: int, bytes_per_value: int, **_) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers that run it."""
    held = _held(sizes)
    seq, sparse = held["sequence_length"], held["sparse"]
    tokens = rows * seq
    n_sparse, n_lightning = _layers(sizes)
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    heads, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
    q = held["ssd_chunk"]
    pooled_seen = 0 if seq <= sparse["dense_len"] else sum(
        max(0, (t + 1 - sparse["kernel_size"]) // sparse["kernel_stride"] + 1)
        for t in range(seq))
    return {
        "sparse_select": {
            "flops": 2 * n_sparse * rows * hq * hd * pooled_seen,
            "bytes": n_sparse * tokens * (hq + hkv) * hd * bytes_per_value},
        "sparse_attention": {
            "flops": 2 * n_sparse * rows * hq * 2 * hd * keys_read(sizes),
            "bytes": n_sparse * tokens * 2 * (hq + hkv) * hd
            * bytes_per_value},
        "lightning_scan": {
            "flops": 2 * n_lightning * tokens * heads
            * (q // 2 * d + q // 2 * d + 2 * d * d),
            "bytes": n_lightning * tokens * 4 * heads * d * bytes_per_value},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    held = _held(sizes)
    seq = held["sequence_length"]
    n_sparse, n_lightning = _layers(sizes)
    p_sparse, p_lightning = mixer_projection_parameters(sizes)
    per_token = 2 * (n_sparse * p_sparse + n_lightning * p_lightning
                     + (n_sparse + n_lightning) * 3 * sizes["hidden_size"]
                     * sizes["intermediate_size"])
    parts = kernels(sizes, 1, 2)
    return (seq * per_token + sum(k["flops"] for k in parts.values())
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])  # the head


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
        + rows * 4 * (held["sequence_length"] + sizes["vocab_size"]),
    }
