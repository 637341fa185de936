"""Share of the rowwise stage's gathers made by one-hot contraction: 100 ×
``PlanStats`` one-hot gathers ÷ (one-hot + take gathers) over the window,
counted per member of each executed plan.  A program without these
counters reads nothing."""


def read(w):
    c = w["counters"]
    onehot, take = c.get("plans.onehot_gathers"), c.get("plans.take_gathers")
    if onehot is None or take is None or onehot + take == 0:
        return None
    return 100.0 * onehot / (onehot + take)
