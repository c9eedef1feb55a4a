"""The largest expert's pairs over the mean of all routed experts', a step
(the mean over the expert layers): the program's counter
``moe.load_max_over_mean`` rises by a step's value at each report, so its
total over its number of rises."""


def read(ctx):
    from benchmark import program_spans

    rises = [e["args"]["value"] for e in program_spans.events(ctx)
             if e.get("ph") == "C" and e["name"] == "moe.load_max_over_mean"]
    if rises:
        return rises[-1] / len(rises)
