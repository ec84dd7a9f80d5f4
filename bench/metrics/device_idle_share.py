"""device_idle_share: the device (one v5e).

Share of the traced window in which no operation ran on the chip, in %:
one minus the union of device-op intervals over the window.
"""


def read(tr):
    if not tr.ops or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s() / tr.window_s) * 100.0
