"""Operations and least bytes of one Vision Transformer step, from shapes.

Operations are those the algorithm needs: two per multiply-add of every
matrix product (patch embedding, q/k/v/o projections, both attention
contractions, the two MLP layers, the head on the class token). Softmax,
LayerNorm, GELU and the residual adds are left out: they are a fraction of
a percent and run on the vector unit, not the matrix unit the peak is of.

Bytes are the least a step must move between memory and the chip: every
parameter once, in the type it is served in; the batch in, in that type; the
probabilities out in float32. Activations are assumed to stay on the chip,
so the bound is a floor and a share of it cannot be flattered.
"""

import re


def parameters(sizes: dict) -> int:
    d, m = sizes["hidden_size"], sizes["intermediate_size"]
    patch, c = sizes["patch_size"], sizes["num_channels"]
    seq = (sizes["image_size"] // patch) ** 2 + 1
    block = 4 * (d * d + d) + (d * m + m) + (m * d + d) + 4 * d
    return (patch * patch * c * d + d          # patch embedding
            + d + seq * d                      # class token, positions
            + sizes["num_hidden_layers"] * block
            + 2 * d                            # final LayerNorm
            + d * sizes["num_labels"] + sizes["num_labels"])


def flops_per_row(sizes: dict) -> int:
    d, m = sizes["hidden_size"], sizes["intermediate_size"]
    patch, c = sizes["patch_size"], sizes["num_channels"]
    n = (sizes["image_size"] // patch) ** 2
    seq = n + 1
    block = 2 * seq * (4 * d * d + 2 * d * m) + 4 * seq * seq * d
    return (2 * n * patch * patch * c * d
            + sizes["num_hidden_layers"] * block
            + 2 * d * sizes["num_labels"])


def rows_per_step(op_names: list, sizes: dict):
    """The batch a compiled program was built for, read off the shapes in
    its operations' names: the commonest ``B`` among ``[B,<tokens>,<width>]``.
    None where no operation names such a shape."""
    seq = (sizes["image_size"] // sizes["patch_size"]) ** 2 + 1
    found = re.findall(rf"\[(\d+),{seq},{sizes['hidden_size']}\]",
                       " ".join(op_names))
    if not found:
        return None
    return int(max(set(found), key=found.count))


def counts(sizes: dict, rows: int, steps: int, bytes_per_value: int) -> dict:
    """``rows`` instances served in ``steps`` executions of the program."""
    image = sizes["image_size"] ** 2 * sizes["num_channels"]
    return {
        "flops": rows * flops_per_row(sizes),
        "bytes": steps * parameters(sizes) * bytes_per_value
        + rows * (image * bytes_per_value + sizes["num_labels"] * 4),
    }
