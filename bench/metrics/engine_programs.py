"""Compiled programs the engine stage's jit holds after the run
(``stats()['stage_programs']['engine']``): each beyond the first is a
compile that set-up pays again.  Silent where the program does not count
them."""


def read(run):
    programs = (run.stats or {}).get("stage_programs") or {}
    return programs.get("engine")
