"""Peak device memory of the compiled step, GB (1e9 bytes): arguments +
outputs − aliased + temporaries from ``compiled.memory_analysis()``.  At a
fixed batch this only says how much larger a batch would fit."""


def read(run):
    peak = run.get("compiled_peak_bytes")
    return None if peak is None else peak / 1e9
