"""Seconds of ``Session.plan`` in set-up, host clock."""


def read(run):
    return run.setup.get("plan_s")
