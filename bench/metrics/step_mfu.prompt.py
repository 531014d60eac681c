"""Model step: the whole step's share of the chip's bf16 peak, read as
`step_mfu` reads it, for the cells judged by prompt tokens/s."""

from bench.window import reader

read = reader("step_mfu")
