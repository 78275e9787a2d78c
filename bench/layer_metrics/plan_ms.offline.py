"""plan_ms.offline: mean per window batch of the program's
``serve.dispatch.plan`` span; see bench/program_spans.py."""

import program_spans


def read(rec):
    return program_spans.stage_ms(rec, "serve.dispatch.plan")
