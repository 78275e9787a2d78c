"""fdr_ms.offline: mean per window batch of the program's
``serve.finalize.fdr`` span; see bench/program_spans.py."""

import program_spans


def read(rec):
    return program_spans.stage_ms(rec, "serve.finalize.fdr")
