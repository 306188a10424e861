"""serve.sample_idle_share: the seconds in which the device ran nothing
inside the program's ``engine.sample`` spans (logits to the host, the
argmax, the emits) over the traced window's seconds, in percent."""

from bench import program_spans


def read(run):
    return program_spans.idle_share(run, "engine.sample")
