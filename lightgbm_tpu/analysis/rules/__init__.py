"""Rule modules. Importing this package registers every rule
(``@register`` in each module populates ``core.REGISTRY``). New rules:
drop a module here, import it below, ship fixtures — see
docs/Static-Analysis.md "Adding a rule"."""

from . import (atomic_writes, collectives, config_doc,
               determinism, journal_schema, precision,
               prom_naming, trace_context, unbounded_io)  # noqa: F401
