"""How unevenly the router loads the held experts in decode: the rows of
the busiest held expert over the mean of the held experts, by expert
layer and decode step, averaged over the window's decode steps — from the
decode program's own counter (it returns, beside the tokens, the rows
each held expert took; the executor keeps the running sums the harness
reads at the window's two ends).  1.0 is an even load; an expert kernel
that pads every expert to the busiest pays this factor."""


def read(record, cell, peaks):
    experts = record["facts"].get("experts")
    if not experts or not experts["steps"]:
        return None
    return experts["max_over_mean"] / experts["steps"]
