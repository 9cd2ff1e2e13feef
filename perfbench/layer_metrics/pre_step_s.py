"""Seconds from `train()`'s entry to the start of step 1: building the data
set's loader, `create_train_state` (eager `model.init`), the step's build."""


def read(run):
    if not run["records"]:
        return None
    first = run["records"][0]
    return first["t"] - first["step_s"] - run["t_train_entry"]
