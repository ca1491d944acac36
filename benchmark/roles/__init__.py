"""Role adapters: how the harness starts one of the program's roles in this
process, which batch source it wraps, and how the role is held to its plain
reference. One module per role, found by the name in a configuration file."""
