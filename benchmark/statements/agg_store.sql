SELECT ss_store_sk, COUNT(*) AS cnt, SUM(ss_quantity) AS qty,
       SUM(ss_ticket_number) AS tickets
FROM store_sales GROUP BY ss_store_sk
