"""Device: the idle share of the traced window, in %, read as
`device_idle_share.closed` reads it, for the cells judged by prompt
tokens/s."""

from bench.window import reader

read = reader("device_idle_share.closed")
