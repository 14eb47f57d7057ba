"""Layer: kernels. Source: program_span (`sequence.step`,
serving/sequence.py, carries `pages_visited`: the KV pages the decode
step's attention read, each live slot's live pages where the pallas
kernels run and its whole table where paged_attend does; and
`pages_table`: live slots x table width). The window's
sum(pages_visited) / sum(pages_table) in percent: 100 says the step
walks every table whatever the contexts' lengths, the share of live
pages says it reads only those. None where the spans lack the two
numbers (a program from before they were recorded) and where the ring
dropped spans. Derived, not measured: the spans restate the
dispatcher's choice by the kernels' own rule
(ops/pallas_attention.py::paged_pages_visited) and nothing counts pages
on the device, so a kernel that walked the whole table again would
still read the live share here. That the pages are skipped shows in
the device trace: `paged_decode_roofline` and the step's `device_ops`.
Moves: output_tokens_per_s."""

from deeplearning4j_tpu.runtime import telemetry


def read(run):
    if telemetry.get_registry().trace.dropped:
        return None
    args = [s["args"] for s in run.program_spans("sequence.step")]
    if not args or any("pages_visited" not in a or "pages_table" not in a
                       for a in args):
        return None
    table = sum(a["pages_table"] for a in args)
    return 100.0 * sum(a["pages_visited"] for a in args) / table \
        if table else None
