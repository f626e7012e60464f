"""The yardstick: everything the benchmark measures with lives here and
imports nothing of ``paddle_tpu`` (the builders do that)."""
