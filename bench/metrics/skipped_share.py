"""Share of the engine's trials skipped at the degree or supernode bounds
(``skipped / trials`` from ``stats()`` after the window), in %."""


def read(run):
    s = run.stats
    if not s or not s["trials"]:
        return None
    return 100.0 * s["skipped"] / s["trials"]
