"""Extra on-device exchange rounds of the route stage per chunk
(``stats()['router_drain_rounds']``, read after the window, over every
chunk the run handed in)."""


def read(run):
    if not run.stats or not run.chunks:
        return None
    return run.stats["router_drain_rounds"] / len(run.chunks)
