"""Share of the traced window in which no operation ran on the chips:
1 - busy / window, busy being the union of the ops' device intervals."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red.busy_s <= 0:
        return None
    return 100.0 * red.idle_share
