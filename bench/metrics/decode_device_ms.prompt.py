"""Model step: device time of the fused decode program, read as
`decode_device_ms` reads it, for the cells judged by prompt tokens/s."""

from bench.window import reader

read = reader("decode_device_ms")
