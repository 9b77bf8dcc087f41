"""Device ms per run of the train step under its ``trunk`` named scope:
the token-embedding gather, the layers' forward and backward, and the
embedding's sparse rows. Exclusive time of the step's ops in the traced
window, attributed by the compiled module's op metadata
(``chipbench/scopes.py``), over the step's runs."""


def read(ctx):
    split = ctx.get("scopes")
    return None if not split else 1e3 * split["trunk"]
