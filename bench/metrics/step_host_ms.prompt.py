"""Scheduler: host time per engine step, read as `step_host_ms` reads
it, for the cells judged by prompt tokens/s."""

from bench.window import reader

read = reader("step_host_ms")
