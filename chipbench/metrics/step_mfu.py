"""Device: the target's model operations in the window (the served form's
``model_ops``: per prompt token of requests whose first token arrived in
the window, and per output token delivered in it) over the window, over the
chip's int8 peak, in percent.  The target's matmuls are int8 x int8 ->
int32."""


def read(ctx):
    positions = ctx.output_positions_in_window()
    for r in ctx.first_token_in_window():
        positions.extend(range(len(r.req.prompt)))
    if not positions:
        return None
    ops = ctx.served.model_ops(ctx.model, positions)
    return 100.0 * ops / ctx.window_s / ctx.peaks["int8_ops_per_s"]
