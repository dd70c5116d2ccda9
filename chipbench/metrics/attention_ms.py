"""Device self time of the program's ``attention`` scope per traced step,
averaged over the devices (``scopes.reduce``). Nothing to read where the
trace has no op under that scope."""

from chipbench import scopes


def read(rec):
    return scopes.scope_ms(rec["trace"], "attention")
