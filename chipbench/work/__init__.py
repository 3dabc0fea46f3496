"""Required work (operations and bytes) of a call, from the configuration's
shapes, one module per model family."""
