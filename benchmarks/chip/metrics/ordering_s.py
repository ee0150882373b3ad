"""Seconds of the program's fill-reducing ordering call in set-up
(``repro.sparse`` ordering named by the configuration), host clock."""


def read(run):
    return run.setup.get("ordering_s")
