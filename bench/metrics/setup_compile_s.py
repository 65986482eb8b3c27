"""Seconds JAX spent compiling or loading programs from the persistent
cache during set-up (its ``backend_compile_duration`` events)."""


def read(run, records, summary):
    return records.get("setup_compile_s")
