"""Host milliseconds per tick inside ``FleetService.collect``: the wait for
the oldest tick's outputs and, with capture control, the high-precision
capture (``stream.collect_hp``). Read from the ``bench.collect`` spans."""


def read(ctx):
    span = ctx["trace"]["spans"].get("collect")
    if not span or not span["count"]:
        return None
    return span["seconds"] / span["count"] * 1e3
