"""Host milliseconds per tick inside ``FleetService.dispatch``: assembly of
the super-chunk, its upload, and the launch of the ADC convert and the
fleet step. Read from the benchmark's ``bench.dispatch`` spans."""


def read(ctx):
    span = ctx["trace"]["spans"].get("dispatch")
    if not span or not span["count"]:
        return None
    return span["seconds"] / span["count"] * 1e3
