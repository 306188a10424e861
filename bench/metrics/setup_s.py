"""setup_s: seconds from process start to the window's start: loading,
making inputs and weights, compiling or loading compiled programs, warming
up and any lead-in traffic."""


def read(run):
    return run.setup_s
