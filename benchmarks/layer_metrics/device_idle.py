"""Layer: device. Share of the traced slice in which no operation ran on the
chip: 1 − (union of device-busy intervals) / slice. One reduction under two
names, because the cells that report it steer different end-to-end metrics
(serving cells: the token gap; batch cells: tokens per second)."""

NAMES = ("device_idle_share.serve", "device_idle_share.batch")


def read(ctx: dict) -> dict:
    t = ctx["trace"]
    idle = 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    return {name: idle for name in NAMES}
