"""Share of the engine rounds' slots that held a change, in %: the changes
handed in (every chunk, warm-up included) over ``stats()['engine_rounds']``
x ``n_shards`` x ``batch``, read after the window.  A round pays every
slot, filled or not.  Silent where the program does not count engine
rounds."""


def read(run):
    s = run.stats
    if not s or not s.get("engine_rounds"):
        return None
    changes = sum(len(c) for c in run.chunks)
    slots = s["engine_rounds"] * s["n_shards"] * run.summ.cfg.batch
    return 100.0 * changes / slots
