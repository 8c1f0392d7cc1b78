"""Error hierarchy spanning every layer, with HTTP status mapping.

Behavioral parity with reference ``crates/core/src/error.rs:6-141``: seven
error families (server, API, validation, queue, batcher, cache, worker,
stream), an ``ApiError -> (HTTP status, error-type string)`` mapping
(``error.rs:39-56``), and stable machine-readable ``code`` strings used in
JSON error bodies (``models.rs:231-261``).

Python exceptions replace Rust enums; each class carries a ``code`` for the
wire format. ``ApiError.status_code()`` / ``error_type()`` reproduce
400/503/408/500 and ``invalid_request_error`` / ``rate_limit_error`` /
``timeout_error`` / ``server_error`` exactly.
"""

from __future__ import annotations


# ---------------------------------------------------------------------------
# Top-level server errors (internal; reference error.rs:6-21)
# ---------------------------------------------------------------------------


class ServerError(Exception):
    """Internal server error, not exposed to clients directly."""


class ConfigError(ServerError):
    def __init__(self, detail: str):
        super().__init__(f"Configuration error: {detail}")
        self.detail = detail


class ModelLoadError(ServerError):
    def __init__(self, detail: str):
        super().__init__(f"Model load error: {detail}")
        self.detail = detail


class WorkerFailure(ServerError):
    def __init__(self, detail: str):
        super().__init__(f"Worker error: {detail}")
        self.detail = detail


class IoError(ServerError):
    def __init__(self, detail: str):
        super().__init__(f"IO error: {detail}")
        self.detail = detail


# ---------------------------------------------------------------------------
# Validation errors (reference error.rs:59-77)
# ---------------------------------------------------------------------------


class ValidationError(Exception):
    """Base class for request-validation failures. ``code`` is the stable
    machine-readable string placed in the JSON error body."""

    code = "validation_error"


class InvalidJson(ValidationError):
    code = "invalid_json"

    def __init__(self, detail: str):
        super().__init__(f"Invalid JSON: {detail}")
        self.detail = detail


class MissingField(ValidationError):
    code = "missing_field"

    def __init__(self, field: str):
        super().__init__(f"Missing required field: {field}")
        self.field = field


class TokenLimitExceeded(ValidationError):
    code = "token_limit_exceeded"

    def __init__(self, actual: int, limit: int):
        super().__init__(f"Token limit exceeded: {actual} tokens > {limit} max")
        self.actual = actual
        self.limit = limit


class InvalidParameter(ValidationError):
    code = "invalid_parameter"

    def __init__(self, field: str, reason: str):
        super().__init__(f"Invalid parameter '{field}': {reason}")
        self.field = field
        self.reason = reason


class EmptyPrompt(ValidationError):
    code = "empty_prompt"

    def __init__(self) -> None:
        super().__init__("Empty prompt not allowed")


# ---------------------------------------------------------------------------
# API-level errors -> HTTP responses (reference error.rs:24-56)
# ---------------------------------------------------------------------------


class ApiError(Exception):
    """API-level error returned to the client as an HTTP response."""

    def status_code(self) -> int:
        raise NotImplementedError

    def error_type(self) -> str:
        raise NotImplementedError

    def code(self) -> str:
        return "api_error"


class ValidationApiError(ApiError):
    """Wraps a ValidationError; HTTP 400 / invalid_request_error
    (error.rs:41,51)."""

    def __init__(self, cause: ValidationError):
        super().__init__(f"Validation error: {cause}")
        self.cause = cause

    def status_code(self) -> int:
        return 400

    def error_type(self) -> str:
        return "invalid_request_error"

    def code(self) -> str:
        return self.cause.code


class QueueFullApiError(ApiError):
    """HTTP 503 / rate_limit_error (error.rs:42,52)."""

    def __init__(self) -> None:
        super().__init__("Queue full, server is overloaded")

    def status_code(self) -> int:
        return 503

    def error_type(self) -> str:
        return "rate_limit_error"

    def code(self) -> str:
        return "queue_full"


class AdmissionShedApiError(ApiError):
    """HTTP 503 / rate_limit_error with a ``Retry-After`` hint and the
    DISTINCT ``admission_shed`` code: deadline-aware admission control
    (serving/health.py) decided the request's queue-wait estimate
    already blows its SLO-derived deadline — "the fleet declined you in
    microseconds, retry after the backlog drains" is actionable in a
    way the generic ``queue_full`` backpressure is not."""

    def __init__(self, retry_after_s: float = 1.0):
        super().__init__(
            "Request shed at admission: the current queue-wait estimate "
            "exceeds this request's latency deadline"
        )
        self.retry_after_s = max(1.0, retry_after_s)

    def status_code(self) -> int:
        return 503

    def error_type(self) -> str:
        return "rate_limit_error"

    def code(self) -> str:
        return "admission_shed"


class RequestTimeoutApiError(ApiError):
    """HTTP 408 / timeout_error (error.rs:43,53)."""

    def __init__(self) -> None:
        super().__init__("Request timeout")

    def status_code(self) -> int:
        return 408

    def error_type(self) -> str:
        return "timeout_error"

    def code(self) -> str:
        return "request_timeout"


class InternalApiError(ApiError):
    """HTTP 500 / server_error (error.rs:44,54)."""

    def __init__(self, detail: str):
        super().__init__(f"Internal server error: {detail}")
        self.detail = detail

    def status_code(self) -> int:
        return 500

    def error_type(self) -> str:
        return "server_error"

    def code(self) -> str:
        return "internal_error"


# ---------------------------------------------------------------------------
# Queue errors (reference error.rs:80-90)
# ---------------------------------------------------------------------------


class QueueError(Exception):
    pass


class QueueFull(QueueError):
    def __init__(self) -> None:
        super().__init__("Queue is full")


class QueueRequestNotFound(QueueError):
    def __init__(self, request_id: str):
        super().__init__(f"Request not found: {request_id}")
        self.request_id = request_id


class RequestCancelled(QueueError):
    def __init__(self) -> None:
        super().__init__("Request cancelled")


# ---------------------------------------------------------------------------
# Batcher errors (reference error.rs:93-99)
# ---------------------------------------------------------------------------


class BatcherError(Exception):
    pass


class BatchTimeout(BatcherError):
    def __init__(self) -> None:
        super().__init__("Batch timeout")


class ChannelClosed(BatcherError):
    def __init__(self) -> None:
        super().__init__("Channel closed")


# ---------------------------------------------------------------------------
# Cache errors (reference error.rs:102-112)
# ---------------------------------------------------------------------------


class CacheError(Exception):
    pass


class CacheSerializationError(CacheError):
    def __init__(self, detail: str):
        super().__init__(f"Serialization error: {detail}")
        self.detail = detail


class CacheDeserializationError(CacheError):
    def __init__(self, detail: str):
        super().__init__(f"Deserialization error: {detail}")
        self.detail = detail


class CacheFull(CacheError):
    def __init__(self) -> None:
        super().__init__("Cache full")


# ---------------------------------------------------------------------------
# Worker errors (reference error.rs:115-128)
# ---------------------------------------------------------------------------


class WorkerError(Exception):
    pass


class ModelNotLoaded(WorkerError):
    def __init__(self) -> None:
        super().__init__("Model not loaded")


class InferenceFailed(WorkerError):
    def __init__(self, detail: str):
        super().__init__(f"Inference failed: {detail}")
        self.detail = detail


class WorkerShutdown(WorkerError):
    def __init__(self) -> None:
        super().__init__("Worker shutdown")


class OutOfMemory(WorkerError):
    def __init__(self) -> None:
        super().__init__("Out of memory")


# ---------------------------------------------------------------------------
# Stream errors (reference error.rs:131-141)
# ---------------------------------------------------------------------------


class StreamError(Exception):
    pass


class ClientDisconnected(StreamError):
    def __init__(self) -> None:
        super().__init__("Client disconnected")


class StreamNotFound(StreamError):
    def __init__(self, request_id: str):
        super().__init__(f"Stream not found: {request_id}")
        self.request_id = request_id


class StreamSendFailed(StreamError):
    def __init__(self) -> None:
        super().__init__("Send failed")
