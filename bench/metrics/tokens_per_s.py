"""tokens_per_s: output tokens emitted in the window / window seconds
(the window ends with the last step begun in it)."""


def read(ctx):
    s = ctx.served
    return s.tokens_in_window / s.window_s if s.window_s > 0 else None
