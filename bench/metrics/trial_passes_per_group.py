"""Trial passes per trial group the engine rounds paid for:
``stats()['trial_passes']`` over ``engine_rounds`` x ``n_shards`` x 2 x
``batch`` (two endpoints per slot, filled or not), read after the window.
A live group runs about one pass plus one per committed move; a padding
slot runs none.  Silent where the program does not count passes (a
program older than them, or the serial layout, which runs none)."""


def read(run):
    s = run.stats
    if not s or not s.get("engine_rounds") or s.get("trial_passes") is None:
        return None
    groups = s["engine_rounds"] * s["n_shards"] * 2 * run.summ.cfg.batch
    return s["trial_passes"] / groups
