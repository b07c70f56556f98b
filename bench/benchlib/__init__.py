"""The benchmark's own code: everything a later change to the program may
not alter (load generation, device gate and peaks, FLOP and byte counts,
trace reduction, plain references and the comparison that decides
``correct``)."""
