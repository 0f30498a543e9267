"""Pure-Python BLS12-381 (fields, curve, hash-to-curve, projective Miller
loop): a copy of the program's host oracle taken at PR 25, imports
rewritten so that it loads nothing of ``lighthouse_tpu``.  The benchmark's
plain reference; later PRs may change the program's copy, never this one."""
