"""Operations and bytes the algorithm needs, worked out from shapes.

Only unpadded widths count: a lane padded to a wider party, a bucket
padded to a power of two or a dead lane adds nothing here.  Matmuls count
2 operations per multiply-add; a backward pass counts twice its forward
matmuls (the gradients of the input and of the weight).  Elementwise work
is left out of the operation count.  Bytes are the least HBM traffic of a
whole fit: every row read once an epoch (its training pass and its
validation pass), and the weights with both Adam moments read and written
once.  A fit's state and a step's activations fit in the chip's on-chip
memory, so nothing more is required; an engine that moves them between
HBM and the cores every step pays for that above this bound.
"""
from __future__ import annotations

F32 = 4


def mlp_matmul_flops(widths, rows: int) -> float:
    """Forward matmul operations of one MLP over ``rows`` rows."""
    return 2.0 * rows * sum(a * b for a, b in zip(widths[:-1], widths[1:]))


def ae_widths(enc_widths) -> list:
    """Encoder widths followed by the mirrored decoder."""
    enc = list(enc_widths)
    return enc + enc[::-1][1:]


def ae_step_flops(enc_widths, rows: int) -> float:
    """One Adam step of a symmetric autoencoder on ``rows`` rows: the
    forward pass and a backward pass of twice its matmuls."""
    return 3.0 * mlp_matmul_flops(ae_widths(enc_widths), rows)


def ae_params(enc_widths) -> int:
    w = ae_widths(enc_widths)
    return sum(a * b + b for a, b in zip(w[:-1], w[1:]))


def ae_eval_flops(enc_widths, rows: int) -> float:
    """A forward pass of the autoencoder (validation loss)."""
    return mlp_matmul_flops(ae_widths(enc_widths), rows)


def stage_fit_flops(enc_widths, n_rows: int, *, batch_size: int,
                    epochs: int, val_frac: float = 0.1) -> float:
    """One stage's fit: ``epochs`` epochs of whole batches over the
    training split plus a validation pass per epoch."""
    n_val = max(int(n_rows * val_frac), 1)
    n_tr = n_rows - n_val
    bs = min(batch_size, n_tr)
    steps = n_tr // bs
    return epochs * (steps * ae_step_flops(enc_widths, bs)
                     + ae_eval_flops(enc_widths, n_val))


def stage_fit_bytes(enc_widths, n_rows: int, *, batch_size: int,
                    epochs: int, row_width: int = 0,
                    val_frac: float = 0.1) -> float:
    """One stage's fit: each epoch reads the rows its batches and its
    validation pass use (``row_width`` floats each, the input width when
    0), and the weights and both Adam moments make one round trip."""
    n_val = max(int(n_rows * val_frac), 1)
    n_tr = n_rows - n_val
    bs = min(batch_size, n_tr)
    rows = (n_tr // bs) * bs + n_val
    return F32 * (epochs * rows * (row_width or enc_widths[0])
                  + 6.0 * ae_params(enc_widths))


def probe_flops(n_rows: int, d: int, n_classes: int, *, folds: int,
                steps: int) -> float:
    """The k-fold logistic probe: per fold and step, the logits and the
    weight gradient over the fold's training rows, then the test rows'
    logits."""
    n_tr = n_rows - n_rows // folds
    per_step = 2.0 * 2.0 * n_tr * d * n_classes
    return folds * (steps * per_step + 2.0 * (n_rows - n_tr) * d * n_classes)


def protocol_fit_flops(cfg: dict) -> dict:
    """Required operations of one seed lane of the protocol, by stage."""
    hp = cfg["train"]
    kw = dict(batch_size=hp["batch_size"], epochs=hp["max_epochs"])
    da = cfg["n_active_features"]
    dp = cfg["dataset"]["d"] - da
    n_rows = cfg["active_rows"]
    al = cfg["n_aligned"]
    enc = cfg["encoders"]
    g1a = [da, *enc["g1_active"]]
    g1p = [dp, *enc["g1_passive"]]
    g2 = [g1a[-1] + g1p[-1], *enc["g2"]]
    g3 = [da, *enc["g3"]]
    out = {
        "g1_active": stage_fit_flops(g1a, n_rows, **kw),
        "g1_passive": stage_fit_flops(g1p, n_rows, **kw),
        "g2": stage_fit_flops(g2, al, **kw),
        "g3": stage_fit_flops(g3, n_rows, **kw),
        "probe": probe_flops(n_rows, g3[-1], cfg["dataset"]["n_classes"],
                             folds=cfg["probe"]["folds"],
                             steps=cfg["probe"]["steps"]),
    }
    out["engine_bytes"] = (
        stage_fit_bytes(g1a, n_rows, **kw)
        + stage_fit_bytes(g1p, n_rows, **kw)
        + stage_fit_bytes(g2, al, **kw)
        # g3's rows carry the teacher's latent and the aligned flag
        + stage_fit_bytes(g3, n_rows, row_width=da + g2[-1] + 1, **kw))
    return out
