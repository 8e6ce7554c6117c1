"""The saturated cell's reading of serve.decode_step_device_ms."""

from harness.loader import load_reader

read = load_reader("serve.decode_step_device_ms")
