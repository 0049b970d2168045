"""Share of the slot-frames the gate step scored that an active sensor's
LP ADC converted, in percent: the step's useful work over the work it
did. The program's ``serve.sampled_frames`` counter over
``serve.scored_frames``."""

from bench import program


def read(ctx):
    share = program.ratio(("serve.sampled_frames",), "serve.scored_frames")
    return None if share is None else share * 100.0
