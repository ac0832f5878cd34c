"""Host time of ``ContinuousServer.register``: planning and
materializing the family (``serve/family.py``, ``core/planner.py``)."""


def read(run):
    return run.setup.get("register")
