"""serve.cache_write_idle_share: the seconds in which the device ran nothing
inside the program's ``engine.write_ragged`` spans (the eager unpack of an
admission wave's KV rows into the slots) over the traced window's seconds,
in percent."""

from bench import program_spans


def read(run):
    return program_spans.idle_share(run, "engine.write_ragged")
