"""Share of the query-key pairs the attention grid computes that the mask
then throws away: 1 - allowed / computed of the program's ``attn.pairs``
counter for calls of the job's sequence length. What block skipping
leaves on the table. The counter rises where a call is traced, before
the window opens, so the series is read as the worker's file ends (a
ratio: how often the call was traced cancels)."""


def read(ctx):
    from benchmark import program_spans

    totals = [e["args"] for e in program_spans.events(ctx)
              if e.get("ph") == "C" and e["name"] == "attn.pairs"]
    if not totals:
        return None
    seq = ctx.cell["job"]["sequence"]
    allowed = totals[-1].get(f"kind=allowed,seq={seq}")
    computed = totals[-1].get(f"kind=computed,seq={seq}")
    if allowed and computed:
        return 100.0 * (1.0 - allowed / computed)
