"""CRUSH and ladder programs the mapping path traced while the window
was open, by the program's own counter.  Has to read 0, beside
`setup.compiles_in_window.epoch`: an edited CRUSH map is served by the
programs the first map built.  Nothing on a program that does not count
them."""


def read(r):
    key = "mapping.crush_program_builds"
    return float(r.delta(key)) if key in r.after else None
