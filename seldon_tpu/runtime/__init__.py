from seldon_tpu.runtime.user_model import SeldonComponent, SeldonNotImplementedError

# Threads of the REST wrapper's default executor (wrapper.build_rest_app):
# how many unit calls, a /generate among them, run at once. A unit reports
# it in /metadata so a client need not discover it by load.
REST_WORKERS = 8

__all__ = ["REST_WORKERS", "SeldonComponent", "SeldonNotImplementedError"]
