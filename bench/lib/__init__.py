"""The benchmark's own machinery: run flow, traffic drivers, spans,
trace reduction, peaks and work counts. Imports nothing of the program
at module level."""
