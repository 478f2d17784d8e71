"""Share of the trial iterations the engine rounds paid for that ran a
trial, in %: ``stats()['trials']`` over ``engine_rounds`` x ``n_shards``
x 2 x ``batch`` x ``c`` (two endpoints per slot, ``c`` samples each), read
after the window.  Silent where the program does not count engine
rounds."""


def read(run):
    s = run.stats
    if not s or not s.get("engine_rounds"):
        return None
    cfg = run.summ.cfg
    paid = s["engine_rounds"] * s["n_shards"] * 2 * cfg.batch * cfg.c
    return 100.0 * s["trials"] / paid
