"""The benchmark's own statement-protocol client: ``POST /v1/statement``,
then ``nextUri`` until the last page.  Stdlib only; what a user's client
does (copied from trino_tpu/server/client.py so that the yardstick's clock
does not move with the program)."""

from __future__ import annotations

import datetime
import decimal
import http.client
import json


class QueryFailed(RuntimeError):
    pass


class Client:
    def __init__(self, host: str, port: int, timeout: float = 1100.0):
        self.host, self.port, self.timeout = host, port, timeout

    def _request(self, method: str, path: str, body=None) -> dict:
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request(method, path, body=body)
            return json.loads(conn.getresponse().read().decode("utf-8"))
        finally:
            conn.close()

    def execute(self, sql: str) -> list:
        """Rows of the finished query, decoded; raises QueryFailed."""
        payload = self._request("POST", "/v1/statement", sql)
        columns, rows = [], []
        while True:
            state = payload.get("stats", {}).get("state")
            if state == "FAILED":
                raise QueryFailed(payload.get("error", {}).get("message", "?"))
            columns = payload.get("columns", columns)
            rows.extend(payload.get("data", []))
            nxt = payload.get("nextUri")
            if nxt is None:
                if state != "FINISHED":
                    raise QueryFailed(f"query ended in state {state}")
                return decode(columns, rows)
            payload = self._request("GET", nxt)


def decode(columns: list, rows: list) -> list:
    """Statement-protocol JSON back to python values (decimals and dates
    travel as strings)."""
    out = []
    for r in rows:
        vals = []
        for c, v in zip(columns, r):
            if v is not None and c["type"].startswith("decimal"):
                v = decimal.Decimal(v)
            elif v is not None and c["type"] == "date":
                v = datetime.date.fromisoformat(v)
            vals.append(v)
        out.append(tuple(vals))
    return out
