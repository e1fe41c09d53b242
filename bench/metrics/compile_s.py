"""Seconds the backend spent compiling, or loading compiled programs from
the persistent cache, before the window opened (the compile-duration
listener).  Moves ``setup_s``."""


def read(run: dict):
    return run.get("compile_setup_s")
