"""The saturated cell's reading of serve.device_idle_share (it moves
another end-to-end metric there, so it has a name of its own)."""

from harness.loader import load_reader

read = load_reader("serve.device_idle_share")
