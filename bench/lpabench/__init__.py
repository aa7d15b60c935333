"""The benchmark's own library: specs, device checks, inputs, the plain
reference, the trace reduction and the byte models.  It imports nothing of
the system under test at import time."""
