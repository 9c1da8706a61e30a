"""The family's pipeline stages are the token families' shared ones
(``rnb_tpu/models/token_stages.py``); the final stage learns the
family from the recipe it is pointed at. The names below are the ones
configuration files written before the move give."""

from rnb_tpu.models.token_stages import (  # noqa: F401
    CHUNK, MAX_ROWS, TokenLoader as NemotronTokenLoader,
    PackedPrefill as NemotronPrefill, dispatch_meta, rows_of_tokens)
