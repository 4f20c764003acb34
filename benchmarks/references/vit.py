"""Plain Vision Transformer forward (Dosovitskiy et al. 2020, section 3.1 and
Table 1), float32, ``jax.numpy`` only: no kernels, no batching tricks. The
yardstick's own copy of the mathematics, so a change to the program's model
code cannot move the reference with it.

Reads the parameter tree the program initialises (``embed`` as an HWIO patch
kernel, ``cls``, ``pos``, ``blocks`` of ``ln1/attn{q,k,v,o}/ln2/mlp_in/
mlp_out``, ``ln``, ``head``). Departures from the paper: none in the
mathematics (pre-LN blocks, GELU, learned 1-D position embedding, class
token, LayerNorm epsilon 1e-6 as in the released code); the classification
head is a single linear layer, as at fine-tuning time.
"""

import jax
import jax.numpy as jnp


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _layernorm(p, x, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _attention(p, x, heads):
    b, s, c = x.shape
    d = c // heads

    def split(y):
        return y.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q, k, v = (split(_dense(p[n], x)) for n in "qkv")
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / jnp.sqrt(float(d))
    out = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, -1), v)
    return _dense(p["o"], out.transpose(0, 2, 1, 3).reshape(b, s, c))


def forward(sizes: dict, params, state, x):
    """Class probabilities ``(B, num_labels)`` for images ``(B, H, W, C)``."""
    patch, dim = sizes["patch_size"], sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    b, h, w, c = x.shape
    gh, gw = h // patch, w // patch
    # non-overlapping patches, flattened in (row, column, channel) order,
    # times the patch kernel: the strided convolution written as a matmul
    patches = x.reshape(b, gh, patch, gw, patch, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, gh * gw, patch * patch * c)
    tok = patches @ params["embed"]["w"].reshape(patch * patch * c, dim) \
        + params["embed"]["b"]
    cls = jnp.broadcast_to(params["cls"], (b, 1, dim))
    tok = jnp.concatenate([cls, tok], axis=1) + params["pos"]
    if len(params["blocks"]) != sizes["num_hidden_layers"]:
        raise ValueError("the program's model has another depth than the "
                         "configuration file")
    for blk in params["blocks"]:
        tok = tok + _attention(blk["attn"], _layernorm(blk["ln1"], tok), heads)
        hid = jax.nn.gelu(_dense(blk["mlp_in"], _layernorm(blk["ln2"], tok)))
        tok = tok + _dense(blk["mlp_out"], hid)
    logits = _dense(params["head"], _layernorm(params["ln"], tok)[:, 0])
    return jax.nn.softmax(logits, axis=-1)
