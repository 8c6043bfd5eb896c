"""One file per family of served model, found by the `family` key of a
serving configuration. A family file gives the functions
`manifest.FAMILY_FUNCTIONS` names and nothing else the runner reads; what
the runner measures and counts is the same for every family."""
