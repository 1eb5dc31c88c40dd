"""admit_fill: prompt tokens the admits in the traced stretch prefilled
over the rows they computed: the sum of the ``serve.admit`` spans'
``prompt_tokens`` over the sum of their ``slots`` x ``bucket``."""


def read(ctx):
    admits = [a for a in getattr(ctx.trace, "span_args", lambda _: [])(
        "serve.admit") if "bucket" in a]
    computed = sum(a["slots"] * a["bucket"] for a in admits)
    if computed <= 0:
        return None
    return 100.0 * sum(a["prompt_tokens"] for a in admits) / computed
