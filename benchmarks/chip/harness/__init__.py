"""The chip benchmark's own machinery: spec, data, runs, lookups, reference,
peaks, rooflines, trace reduction.

Nothing here is imported by the system under test, and nothing here is
borrowed from it except the entry points a run drives (``EraIndexer``,
``DeviceIndex``, ``AsyncServer``) and the spans and counters it reads.
"""
