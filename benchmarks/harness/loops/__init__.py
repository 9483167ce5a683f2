"""Loop kinds.  A workload file names one (`"loop": {"kind": ...}`); a new
kind is a new module here with `run(queries, stop, params, annotate)` and
`end_to_end(window)`, not an edit."""
