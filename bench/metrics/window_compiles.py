"""Compilations inside the window: the session's executable and program
compiles plus JAX's compile events.  It should read 0 (layer: session and
program)."""
from __future__ import annotations


def read(r):
    c = r.counters
    return c["compiles"] + c["program_compiles"] + c["jax_compile_events"]
