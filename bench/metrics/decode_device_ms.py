"""Model step: mean device time of the fused decode program
(``jit_mita_decode_step``) over its executions in the traced window, in
ms."""

from bench import program


def read(run):
    return program.module_ms(run, "mita_decode_step")
