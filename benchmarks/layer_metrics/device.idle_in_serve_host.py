"""Of the idle time of the chip that idles most, the share (%) during
which the serve thread is in its own host work: the union of the
program spans `apex.server.stack`, `apex.server.dispatch` and
`apex.server.scatter` on the xplane's host plane, intersected with the
idle gaps the trace reduction found. Also says the whole table — idle
seconds by `apex.*` span, longest first — to stderr. None when the
trace holds no `apex.*` event."""

import json

from benchmarks.harness import program_spans
from benchmarks.harness.device import say


def read(facts: dict) -> float | None:
    table = program_spans.of_facts(facts)
    if table is None:
        return None
    say("idle by program span " + json.dumps(table))
    return 100.0 * table["serve_host_share"]
