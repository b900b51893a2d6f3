"""What the readers of the compile layer share: the program's
``compile_seconds_total`` and ``compile_events_total`` families, by ``stage``
(trace | lower | compile) and by the root program span open when jax heard the
stage, read through ``metrics.counter_value``'s flat names
(``compile_seconds_total{root=train,stage=lower}``). A program without the
families has no such listener: every reader then gives nothing, and so does one
whose metrics are gated off (no stage heard under ``train``, although the
warm-up call always traces)."""

SECONDS = "compile_seconds_total"
EVENTS = "compile_events_total"
STAGES = ("trace", "lower", "compile")


def flat(family: str, stage: str) -> str:
    return f"{family}{{root=train,stage={stage}}}"


def heard() -> bool:
    """Did the program count its compile pipeline under ``train()``?"""
    from h2o3_tpu.utils import metrics

    if SECONDS not in {f.name for f in metrics.REGISTRY.families()}:
        return False
    return sum(metrics.counter_value(flat(EVENTS, s)) for s in STAGES) > 0


def total(family: str, stages):
    """The process's total of ``family`` under ``train()`` over ``stages``,
    read from the registry when the reader runs: the warm-up call's, plus
    what the window's calls added. None where nothing was heard."""
    from h2o3_tpu.utils import metrics

    if not heard():
        return None
    return sum(metrics.counter_value(flat(family, s)) for s in stages)
