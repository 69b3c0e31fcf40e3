"""Host time of compiling the plan into scheduled tiles per job: the
``er.job``, ``er.lower`` and ``er.schedule`` spans (``er/compiler``:
``plan_to_job``, ``lower``, ``schedule_tiles``)."""
import spans


def seconds(sp, trace):
    return spans.seconds(sp, "er.job", "er.lower", "er.schedule")


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
