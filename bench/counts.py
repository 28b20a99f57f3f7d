"""Operations and bytes computed from shapes: the yardstick the per-layer
metrics divide by.  Nothing here reads the program."""
from __future__ import annotations


def dit_forward_flops(cfg: dict, *, paper: bool = False) -> float:
    """FLOPs (2 per multiply-add) of one DiT forward row at the
    configuration's token count, attention scores included.

    ``paper=False`` counts the block as the program runs it: a gated MLP
    with ``d_ff``-wide gate and up matrices, and ``latent_dim`` output
    channels.  ``paper=True`` counts the block of the DiT paper (plain
    two-matrix MLP, learned-sigma output of twice the channels, and the
    adaLN modulation products the paper's counter includes), whose
    multiply-adds are the paper's Gflops column: 118.6 G for DiT-XL/2 at
    256 tokens and 524.6 G at 1024."""
    d, n, L = cfg["d_model"], cfg["num_tokens"], cfg["num_layers"]
    ff, lat = cfg["d_ff"], cfg["latent_dim"]
    hk = cfg["num_heads"] * cfg["head_dim"]
    mlp_mats = 2 if paper else 3
    out_ch = 2 * lat if paper else lat
    per_token = 3 * d * hk + hk * d + mlp_mats * d * ff
    attention = 2 * n * n * hk                  # q k^T and p v
    ada = d * 6 * d                             # once per row
    modulate = 2 * n * d if paper else 0
    layer = n * per_token + attention + ada + modulate
    embed = n * lat * d + 256 * d + d * d + d * 2 * d + n * d * out_ch
    if paper:
        embed += n * d                          # final modulation
    return 2.0 * (L * layer + embed)


def taa_kernel_bytes(T: int, D: int, m: int, lanes: int,
                     itemsize: int = 4) -> dict:
    """HBM bytes one call of each TAA Pallas kernel must move over
    ``lanes`` vmapped lanes: every input read once, every output written
    once.  D is padded to the 128-lane multiple the kernels work on.

    gram:  reads dF (m, T, D), R (T, D), mask (T, 1); writes the
           column-packed (T, 128) Gram/u tile.
    apply: reads x, R (T, D), dX, dF (m, T, D), the (T, 128) gamma tile
           and mask (T, 1); writes the updated (T, D) rows.
    """
    dpad = -(-D // 128) * 128
    gram = (m * T * dpad + T * dpad + T + T * 128) * itemsize
    apply = ((2 + 2 * m) * T * dpad + T * 128 + T + T * dpad) * itemsize
    return {"gram": gram * lanes, "apply": apply * lanes}
