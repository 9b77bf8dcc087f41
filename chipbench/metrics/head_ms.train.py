"""Device ms per run of the train step under its ``head`` named scope,
``head/sample`` included: the candidate gather, scores, loss and
coefficients, the head's sparse rows and the trunk cotangent. Exclusive
time of the step's ops in the traced window, attributed by the compiled
module's op metadata (``chipbench/scopes.py``), over the step's runs."""


def read(ctx):
    split = ctx.get("scopes")
    return None if not split else 1e3 * split["head"]
