"""Device ms per run of the train step under its ``optimizer`` named
scope: the global-norm clip, dense Adagrad on the trunk, the sparse rows
of head and embedding, and the non-finite select. Exclusive time of the
step's ops in the traced window, attributed by the compiled module's op
metadata (``chipbench/scopes.py``), over the step's runs."""


def read(ctx):
    split = ctx.get("scopes")
    return None if not split else 1e3 * split["optimizer"]
