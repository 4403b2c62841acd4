"""compiles_in_window: programs lowered inside the measured window (each
then compiled or read from the persistent cache).  The warm-up is meant
to leave none."""


def read(rec):
    return rec["compiles_in_window"]
