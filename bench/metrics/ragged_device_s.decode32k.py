"""Device seconds a study spends in the engine's ragged program variant:
every ``_ga_program_ragged`` run of the traced (first) study."""


def read(view):
    s = view["trace"].program_s("_ga_program_ragged")
    return s if s > 0 else None
