"""A stdlib HTTP client for the ``/v1`` API: one keep-alive connection.

Kept with the benchmark, not taken from ``repro.service.client``, so that
no change to the program can change the client that measures it.
"""
from __future__ import annotations

import http.client
import json
from typing import Optional, Tuple


class Connection:
    """One persistent HTTP/1.1 connection.  Not thread-safe: give each
    sending thread its own."""

    def __init__(self, host: str, port: int, timeout: float = 120.0):
        self.host, self.port, self.timeout = host, port, timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def raw(self, method: str, path: str,
            body: Optional[dict] = None) -> Tuple[int, bytes]:
        """(status, body bytes); status 0 means no reply.  A GET is sent
        again once on a dropped keep-alive socket; a POST never is, so no
        event is applied twice."""
        data = json.dumps(body).encode() if body is not None else None
        headers = {"Content-Type": "application/json"} if data else {}
        for attempt in ((0, 1) if method == "GET" else (1,)):
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout)
                self._conn.request(method, path, body=data, headers=headers)
                resp = self._conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    return 0, b""
        return 0, b""

    def request(self, method: str, path: str,
                body: Optional[dict] = None) -> Tuple[int, Optional[dict]]:
        """(status, parsed JSON body, or None where there is none)."""
        status, raw = self.raw(method, path, body)
        try:
            return status, json.loads(raw) if raw else None
        except ValueError:
            return status, None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None
