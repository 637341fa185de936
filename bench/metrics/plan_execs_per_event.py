"""Compiled-plan executions per completed event (``PlanStats`` plan hits
plus plans built; a vmapped batch counts once per member), think-time
executions included."""


def read(w):
    c = w["counters"]
    return (c["plans.plan_hits"] + c["plans.plans_built"]) / w["events"] if w["events"] else None
