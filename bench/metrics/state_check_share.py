"""state_check_share: share of the admit and window programs' device time
spent in ops under the program's ``serve.state_check`` scope (the entry
and exit state checksums and the per-token finiteness check)."""


def read(ctx):
    t = ctx.trace
    if not getattr(t, "scope_time", None):
        return None
    spent = sum(t.program_times("admit")) + sum(t.program_times("window"))
    check = t.scope_seconds("serve.state_check")
    return 100.0 * check / spent if spent > 0 else None
