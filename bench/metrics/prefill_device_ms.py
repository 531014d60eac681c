"""Model step: mean device time of the batched chunk-prefill program
(``jit_mita_batched_chunk_prefill``) over its executions in the traced
window, in ms."""

from bench import program


def read(run):
    return program.module_ms(run, "mita_batched_chunk_prefill")
