"""Bytes the served path put on the device per sensor frame delivered:
the program's ``serve.h2d_bytes`` (dispatch's super-chunk and metadata,
and the HP capture's upload of the raw super-chunk) and
``cascade.h2d_bytes`` (the detector's input blocks), over
``serve.arrival_frames``."""

from bench import program


def read(ctx):
    return program.ratio(("serve.h2d_bytes", "cascade.h2d_bytes"),
                         "serve.arrival_frames")
