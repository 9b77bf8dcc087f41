"""Device ms per run of the train step under its ``head/sample`` named
scope: everything that reads the generator tree (its features, the
negatives drawn, the positives' noise log-probabilities). Exclusive time
of the step's ops in the traced window, attributed by the compiled
module's op metadata (``chipbench/scopes.py``), over the step's runs."""


def read(ctx):
    split = ctx.get("scopes")
    return None if not split else 1e3 * split["head/sample"]
