"""The chip benchmark of the diffusion server (see ``run.py``)."""
