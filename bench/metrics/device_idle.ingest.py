"""Share of the ingest window in which no program ran on the chip, in
percent: one minus the union of the device's module intervals over the
traced window."""


def read(ctx):
    idle = ctx["trace"].idle_share()
    return None if idle is None else 100.0 * idle
