"""Launchers: the mesh constructor, the compile cache, train/serve CLIs."""
