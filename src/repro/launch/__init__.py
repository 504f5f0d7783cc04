"""Launch: production mesh, training and serving entry points."""
