"""Share of the device's busy time, in %, spent in the TAA Pallas kernels
of the Anderson round: every device op whose trace name starts with
``%_taa_`` (the program's ``kernels.ops`` jitted entry points, staged
gram/apply or fused round)."""

PATTERN = "%_taa_"


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["taa"] is None or red.busy_s <= 0:
        return None
    seconds, calls = red.matching(PATTERN)
    if not calls:
        return None
    return 100.0 * seconds / red.busy_s
