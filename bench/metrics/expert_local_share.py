"""Share of the routed (token, expert) pairs that the experts held on this
chip computed, in percent: the program's ``cascade.local_pairs`` over
``cascade.routed_pairs`` (every row's tokens, pad rows included, x top-k x
expert layers). With 8 of 64 experts held and even routing, about 12.5%."""

from bench import program


def read(ctx):
    share = program.ratio(("cascade.local_pairs",), "cascade.routed_pairs")
    return None if share is None else share * 100.0
