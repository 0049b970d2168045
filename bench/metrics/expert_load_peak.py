"""How unevenly the held experts' pairs fall: the program's
``cascade.local_pairs_peak`` (per batch and expert layer, the busiest held
expert's pairs x the experts held) over ``cascade.local_pairs``. 1 where
every held expert gets as many pairs; what a grouped matmul padded to its
largest group would compute, over what it needs."""

from bench import program


def read(ctx):
    return program.ratio(("cascade.local_pairs_peak",), "cascade.local_pairs")
