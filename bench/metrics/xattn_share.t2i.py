"""Cross-attention's share of device time in the text-to-image cells:
device time under ``dit/xattn`` (the q/k/v/o projections and the
``_dit_xflash`` kernel), in % of busy time; see scopes."""
from scopes import for_window


def read(ctx):
    red = for_window(ctx["trace"])
    if red is None or red.busy_s <= 0:
        return None
    seconds = red.ending("dit/xattn")
    if not seconds:
        return None
    return 100.0 * seconds / (red.busy_s * red.devices)
