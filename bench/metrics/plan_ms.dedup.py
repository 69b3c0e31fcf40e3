"""Host time of the paper's Job 1 and plan per job: the ``er.block``,
``er.bdm`` and ``er.plan`` spans (``core/``: blocking keys, the BDM,
PairRange, the features gathered into the plan's order)."""
import spans


def seconds(sp, trace):
    return spans.seconds(sp, "er.block", "er.bdm", "er.plan")


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
