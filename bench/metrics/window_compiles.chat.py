"""Runtime (``runtime/cache.ProgramCache``): programs compiled inside the
window, the ProgramCache's cold compiles plus JAX's backend compiles.
Set-up warms every shape the traffic uses, so this should read 0."""


def read(cell):
    return cell.layer.get("window_compiles")
