"""Temporaries of the compiled ZO step the window runs (GB), from the
compiler's memory analysis of that executable."""


def read(run):
    if run.get("job") != "zo_train":
        return None
    return run["temp_bytes"] / 1e9
