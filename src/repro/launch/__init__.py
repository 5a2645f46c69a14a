"""Launch layer: the train and serve entry points."""
