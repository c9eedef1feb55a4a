"""Share of the pair buffer's rows that hold no routed pair: 1 - held /
buffer of the program's ``moe.pairs`` counter, over every step the run
reported (the counter is cumulative; read as the worker's file ends). What
the static buffer computes beyond what was routed."""


def read(ctx):
    from benchmark import program_spans

    totals = [e["args"] for e in program_spans.events(ctx)
              if e.get("ph") == "C" and e["name"] == "moe.pairs"]
    if not totals:
        return None
    held, buffer = totals[-1].get("kind=held"), totals[-1].get("kind=buffer")
    if held and buffer:
        return 100.0 * (1.0 - held / buffer)
