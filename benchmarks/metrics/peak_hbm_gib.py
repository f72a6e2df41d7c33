"""peak_bytes_in_use of the fullest device after the window."""


def read(ctx):
    b = ctx.get("memory_peak_bytes")
    return b / 2.0**30 if b else None
