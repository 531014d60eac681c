"""Scheduler: host time per engine step in the traced window, in ms: the
mean ``engine.step`` span less the ``backend.download`` spans inside it
(the program's own spans, `repro.serve.spans`)."""

from bench import program


def read(run):
    return program.step_host_ms(run)
