"""Share of the leaf-op device time in the traced window that lies under no
ALS stage scope, in %: code that runs outside every stage. Moves
``iter_s``."""
from chipbench.stages import unscoped_share


def read(ctx):
    if ctx.trace is None:
        return None
    return unscoped_share(ctx.trace)
