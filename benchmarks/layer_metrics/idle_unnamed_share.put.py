"""Of the device's idle time in the traced span, the share under no
`ceph.*` event of any host thread (benchmarks/idle_sections.py); the idle
seconds by section name go to benchmarks/.trace/idle_by_section.json."""

import os

from benchmarks import idle_sections

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".trace")


def read(ctx):
    return idle_sections.read(
        ctx, TRACE_DIR, os.path.join(TRACE_DIR, "idle_by_section.json"))
