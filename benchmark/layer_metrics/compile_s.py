"""Seconds JAX spent compiling, or fetching compiled programs from the
persistent cache, during set-up (``jax.monitoring`` backend-compile
durations). Layer: process start and compile cache. Moves ``setup_s``."""


def read(run: dict):
    return run["compile_s"]
