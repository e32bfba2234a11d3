"""Of set-up's first calls, the share whose compiled program was loaded from the
runner's executable store (``dynamo_tpu/executable_store.py``): 100 x the
``runner_first_call`` spans whose ``store`` is ``hit`` over all of set-up's:
100 in a warm run (nothing was traced or lowered), 0 in a cold one and in a
process without a store (``off``); nothing where the spans carry no ``store``
field (a program from before the store)."""
from benchmark import setup_spans


def read(ctx):
    spans = setup_spans.setup_spans(ctx, setup_spans.FIRST_CALL)
    if spans is None or not any("store" in s for s in spans):
        return None
    return 100.0 * sum(1 for s in spans if s.get("store") == "hit") / len(spans)
