"""The cross-attention kernel's share of its roofline in the text-to-image
cells, in %: the ``_dit_xflash`` calls' least time on the chip, each
max(FLOPs / peak FLOP/s, HBM bytes / peak bytes/s) from the PixArt model
module's counts, over their measured device time.  A call is a device op
whose instruction is the kernel itself (its name starts ``%_dit_xflash``;
an op that only reads the kernel's output names it among its operands).
Every call of the window has one shape: 2 rows (the caption's and the
null caption's) for each lane's evaluated row, all slots at once."""
import json
from pathlib import Path

import harness

BENCH = Path(__file__).resolve().parents[1]
CONFIG = "pixart-alpha-xl2-512"
PREFIX = "%_dit_xflash"


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["peaks"] is None:
        return None
    mine = [n for n in red.op_seconds if n.startswith(PREFIX)]
    seconds = sum(red.op_seconds[n] for n in mine)
    calls = sum(red.op_calls[n] for n in mine)
    if not calls or seconds <= 0:
        return None
    with open(BENCH / "configs" / f"{CONFIG}.json") as f:
        cfg = json.load(f)
    model = harness.load_model(cfg["arch"], BENCH)
    rows = 2 * ctx["slots"] * ctx["rows_per_lane_iter"]
    peaks = ctx["peaks"]
    least = max(model.xflash_flops(cfg, rows) / peaks["flops_per_s"],
                model.xflash_bytes(cfg, rows) / peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
