"""Device idle seconds of the traced part of a snapshot cycle that fall
while the loop thread is in ``input.device_put`` (idle gaps of the device
trace laid against the program's leaf spans on the loop thread's line)."""


def read(ctx):
    from benchmark import program_spans

    return program_spans.idle_seconds(ctx, "input.device_put")
