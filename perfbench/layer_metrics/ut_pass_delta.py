"""How much the LAST pass of a looped encoder still moves the state: the mean
over tokens of |h_T - h_(T-1)| / |h_(T-1)| in the query forward, the program's
stride-gated counter `ut_pass_delta` (step records' `health` block), averaged
over the window's samples. What an exit gate would act on; 0 is a loop whose
last pass does nothing."""

from perfbench import nested_spans


def read(run):
    return nested_spans.counter(run, "ut_pass_delta")
