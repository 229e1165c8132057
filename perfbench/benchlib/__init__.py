"""The benchmark's shared library: specification loading, traffic schedules,
the generator, the sink tailer, the agent child, the comparison that decides
``correct``, the reduction from traces and spans to numbers, statistics."""
